"""The observability plane: span tracing (nesting, rid correlation,
Perfetto export, disabled-mode no-op), histogram quantile math vs
exact samples, the Prometheus/JSON scrape shapes, per-request stage
attribution tiling the measured wall, the timeline SVG, filetest
--trace, and the daemon wire round-trip (scrape + shutdown trace
artifact)."""

import contextlib
import json
import os
import random
import subprocess
import sys
import time

import pytest

from comdb2_tpu.obs import trace
from comdb2_tpu.obs.metrics import (DEFAULT_MS_BUCKETS, Histogram,
                                    Registry)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracing():
    """Enabled tracing scoped to one test — the flag and span buffer
    are process-global."""
    trace.clear()
    trace.enable()
    try:
        yield trace
    finally:
        trace.disable()
        trace.clear()


# --- histogram quantile math -------------------------------------------------

def _bracket(edges, v):
    """(lo, hi) bucket edges containing v."""
    lo = 0.0
    for e in edges:
        if v <= e:
            return lo, e
        lo = e
    return lo, lo


def test_histogram_quantiles_vs_exact_samples():
    """The golden contract: every derived quantile lands inside the
    bucket bracketing the EXACT sample quantile (error <= bucket
    width, as documented)."""
    rng = random.Random(7)
    h = Histogram()
    samples = [rng.uniform(0, 3000) for _ in range(4000)]
    for v in samples:
        h.observe(v)
    samples.sort()
    for q in (0.5, 0.95, 0.99):
        exact = samples[int(q * (len(samples) - 1))]
        lo, hi = _bracket(DEFAULT_MS_BUCKETS, exact)
        est = h.quantile(q)
        assert lo * 0.99 <= est <= hi * 1.01, (q, exact, est, lo, hi)
    assert h.count == 4000
    assert abs(h.sum - sum(samples)) < 1e-6 * sum(samples)


def test_histogram_edges_and_overflow():
    h = Histogram(buckets=(10, 100))
    for v in (5, 50, 500, 5000):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == [[10, 1], [100, 2], ["+Inf", 4]]
    # overflow clamps to the last finite edge — an honest "at least"
    assert h.quantile(0.99) == 100


# --- registry render shapes --------------------------------------------------

def test_registry_prometheus_and_json_shapes():
    r = Registry()
    r.counter("svc_reqs_total", help="requests").inc(3)
    r.gauge("svc_depth").set(7)
    h = r.histogram("svc_lat_ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(5)
    r.gauge("svc_occ", bucket="n16-s8").set(0.5)

    snap = r.snapshot()
    assert snap["svc_reqs_total"]["type"] == "counter"
    assert snap["svc_reqs_total"]["series"][0]["value"] == 3
    s = snap["svc_lat_ms"]["series"][0]
    assert s["count"] == 2 and s["buckets"][-1] == ["+Inf", 2]
    assert snap["svc_occ"]["series"][0]["labels"] == {
        "bucket": "n16-s8"}
    json.dumps(snap)                      # wire-safe

    text = r.render_prometheus()
    assert "# TYPE svc_lat_ms histogram" in text
    assert 'svc_lat_ms_bucket{le="1"} 1' in text
    assert 'svc_lat_ms_bucket{le="+Inf"} 2' in text
    assert "svc_lat_ms_count 2" in text
    assert 'svc_occ{bucket="n16-s8"} 0.5' in text
    # cumulative bucket counts must be monotone
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("svc_lat_ms_bucket")]
    assert cums == sorted(cums)

    with pytest.raises(ValueError):
        r.counter("svc_depth")            # type mismatch is an error


# --- span tracing ------------------------------------------------------------

def test_spans_nest_and_correlate(tracing):
    with trace.request(41):
        with trace.span("outer", k=1):
            with trace.span("inner"):
                time.sleep(0.001)
    trace.record("retro", 1.0, 2.0, rid=9, bytes_h2d=128)
    spans = {s.name: s for s in trace.spans()}
    assert set(spans) == {"outer", "inner", "retro"}
    inner, outer = spans["inner"], spans["outer"]
    assert inner.parent is outer
    assert inner.rid == outer.rid == 41
    # nesting: the child interval is contained in the parent's
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    assert spans["retro"].rid == 9

    doc = trace.export_chrome()
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert ev["inner"]["args"] == {"rid": 41, "parent": "outer"}
    assert ev["retro"]["args"]["bytes_h2d"] == 128
    assert ev["retro"]["dur"] == pytest.approx(1e6)
    json.dumps(doc)                       # Perfetto-loadable JSON


def test_disabled_mode_is_a_noop():
    trace.disable()
    trace.clear()
    # one shared no-op context manager, nothing recorded
    assert trace.span("a") is trace.span("b", k=1)
    with trace.span("a") as s:
        assert s.set(x=1) is s
    trace.record("r", 0.0, 1.0)
    assert trace.spans() == []
    assert not trace.enabled()


def test_annotate_sink_follows_span_nesting(tracing):
    """The profiler sink is entered and exited once per span, with the
    span's name, in nesting order; never from record() and never once
    tracing is off."""
    log = []

    @contextlib.contextmanager
    def annotate(name):
        log.append(("enter", name))
        yield
        log.append(("exit", name))

    @trace.traced("deco")
    def work():
        with trace.span("leaf"):
            pass

    trace.enable(annotate=annotate)
    with trace.span("outer", k=1):
        with trace.span("inner"):
            pass
        work()
    trace.record("retro", 0.0, 1.0)
    assert log == [("enter", "outer"), ("enter", "inner"),
                   ("exit", "inner"), ("enter", "deco"),
                   ("enter", "leaf"), ("exit", "leaf"),
                   ("exit", "deco"), ("exit", "outer")]
    assert {s.name for s in trace.spans()} == {
        "outer", "inner", "deco", "leaf", "retro"}

    trace.disable()
    with trace.span("off"):
        work()
    trace.record("retro-off", 0.0, 1.0)
    trace.enable()                        # no sink: spans only
    with trace.span("plain"):
        pass
    assert len(log) == 8


def test_span_buffer_is_bounded(tracing):
    trace.enable(max_spans=8)
    try:
        for i in range(20):
            with trace.span(f"s{i}"):
                pass
        assert len(trace.spans()) == 8
        assert trace.dropped_spans() == 12
        assert trace.export_chrome()["otherData"][
            "dropped_spans"] == 12
    finally:
        trace.enable()                    # restore default cap


# --- the service surfaces ----------------------------------------------------

def _core(**kw):
    from comdb2_tpu.service import VerifierCore

    kw.setdefault("F", 64)
    kw.setdefault("batch_cap", 8)
    return VerifierCore(**kw)


def _submit(core, h, **fields):
    from comdb2_tpu.ops.history import history_to_edn

    return core.submit({"op": "check",
                        "history": history_to_edn(list(h)),
                        **fields}, time.monotonic())


def test_metrics_kind_scrape_round_trip():
    """Golden shape of the kind:"metrics" reply — and it answers even
    at a full queue (served ahead of backpressure)."""
    from comdb2_tpu.ops.synth import register_history

    core = _core(max_queue=1)
    h = register_history(random.Random(2), 3, 24, p_info=0.0)
    _submit(core, h)
    core.tick()
    _, reply = core.submit({"op": "check", "kind": "metrics",
                            "id": 5}, time.monotonic())
    assert reply["ok"] and reply["kind"] == "metrics"
    assert reply["id"] == 5
    snap = reply["metrics"]
    for name in ("service_queue_wait_ms", "service_host_pack_ms",
                 "service_device_ms", "service_finalize_ms",
                 "service_latency_ms"):
        series = snap[name]["series"][0]
        assert {"count", "sum", "p50", "p95", "p99",
                "buckets"} <= set(series)
    assert snap["service_queue_wait_ms"]["series"][0]["count"] >= 1
    assert snap["service_dispatches_total"]["series"][0]["value"] >= 1
    assert snap["compile_xla_lowerings_total"]["series"][0][
        "value"] >= 0
    assert "service_queue_wait_ms_bucket{" in reply["prometheus"]
    json.dumps(reply)                     # one wire-safe frame
    # scrape while the queue is at cap: still answered, not overload
    assert _submit(core, h)[0] is not None          # fills the queue
    _, r2 = core.submit({"op": "check", "kind": "metrics"},
                        time.monotonic())
    assert r2["ok"] and r2["kind"] == "metrics"
    core.tick()


def test_reply_stages_tile_latency():
    """The attribution contract bench_service asserts at scale: per
    reply, sum(stages) ~= latency_ms."""
    from comdb2_tpu.ops.synth import register_history

    core = _core()
    for seed in (3, 4):
        _submit(core, register_history(random.Random(seed), 3, 24,
                                       p_info=0.0))
    done = core.tick()
    assert done
    for _, reply in done:
        stages = reply["stages"]
        assert set(stages) == {"queue_wait_ms", "host_pack_ms",
                               "device_ms", "finalize_ms"}
        total = sum(stages.values())
        assert abs(total - reply["latency_ms"]) <= \
            max(0.1 * reply["latency_ms"], 5.0), reply
    st = core.status()
    assert st["stage_ms"]["queue_wait"]["n"] >= 2
    assert st["transfer_bytes"]["h2d"] > 0


def test_expired_reply_stages_tile_latency():
    """The expiry path keeps the attribution contract: a deadline-
    expired request's reply carries ALL four stages (its whole wait
    is queue wait; the rest observe as 0), sum(stages) tiles its
    latency_ms, and the stage histograms share the latency
    histogram's count — the 153-vs-258 count mismatch this round
    fixed."""
    from comdb2_tpu.ops.synth import register_history
    from comdb2_tpu.service.core import STAGES

    core = _core()
    h = register_history(random.Random(9), 3, 24, p_info=0.0)
    _submit(core, h, deadline_ms=0)       # expired on arrival
    _submit(core, h)
    time.sleep(0.002)
    done = core.tick()
    expired = next(r for _, r in done if r["valid"] == "unknown")
    assert expired["cause"] == "deadline"
    stages = expired["stages"]
    assert set(stages) == set(STAGES)
    assert stages["queue_wait_ms"] > 0
    assert stages["host_pack_ms"] == stages["device_ms"] == \
        stages["finalize_ms"] == 0.0
    total = sum(stages.values())
    assert abs(total - expired["latency_ms"]) <= \
        max(0.1 * expired["latency_ms"], 5.0), expired
    # histogram counts tile: every stage series counts EVERY
    # completed request, expiries included
    snap = core.metrics_reply()["metrics"]
    n_lat = snap["service_latency_ms"]["series"][0]["count"]
    assert n_lat == len(done) == 2
    for s in STAGES:
        name = "service_" + s.replace("_ms", "") + "_ms"
        assert snap[name]["series"][0]["count"] == n_lat, name


def test_expired_shrink_partial_stages_tile_latency():
    """A shrink job cut by its deadline BETWEEN rounds charges the
    final re-queue wait to queue_wait, so the partial reply's stages
    still tile its latency (review regression — real clocks
    throughout, the stage math and the expiry share one timebase)."""
    import random as _random

    from comdb2_tpu.ops.history import history_to_edn
    from comdb2_tpu.ops.synth import inject_anomaly, register_history
    from comdb2_tpu.service.core import STAGES

    core = _core()
    base = register_history(_random.Random(23), 3, 200,
                            fs=("write",), p_info=0.0)
    h, _ = inject_anomaly(base, "stale-read")
    _, reply = core.submit(
        {"op": "check", "kind": "shrink", "id": 3,
         "history": history_to_edn(h), "deadline_ms": 50},
        time.monotonic())
    assert reply is None
    deadline = time.monotonic() + 120
    done = []
    while not done and time.monotonic() < deadline:
        done = core.pump(time.monotonic())
    (_, r), = done
    if not r.get("partial"):
        pytest.skip("minimization finished inside the deadline — "
                    "nothing expired between rounds")
    assert r["cause"] == "deadline"
    stages = r["stages"]
    assert set(stages) == set(STAGES)
    total = sum(stages.values())
    assert abs(total - r["latency_ms"]) <= \
        max(0.1 * r["latency_ms"], 5.0), r


def test_priming_stays_out_of_the_histograms():
    core = _core()
    core.prime(specs=((24, 2),), seed=41)
    assert core.metrics_reply()["metrics"][
        "service_latency_ms"]["series"][0]["count"] == 0
    records, _ = core.timeline_records()
    assert records == []


def test_timeline_svg_renders_stages_and_events():
    from comdb2_tpu.report.service_svg import render_service_timeline

    records = [{"t": 0.2 + i * 0.1, "lat_ms": 5.0 + i, "kind": "check",
                "valid": True,
                "stages": {"queue_wait_ms": 2.0, "host_pack_ms": 1.0,
                           "device_ms": 2.0, "finalize_ms": 0.1}}
               for i in range(20)]
    events = [{"t": 1.0, "event": "overload"},
              {"t": 1.5, "event": "deadline"}]
    svg = render_service_timeline(records, events)
    assert svg.startswith("<svg")
    assert "queue_wait" in svg and "device" in svg
    assert svg.count("stroke-dasharray") >= 2      # event markers
    # degenerate inputs must not crash the artifact pass
    assert render_service_timeline([], []).startswith("<svg")


def test_filetest_trace_artifact(tmp_path):
    """filetest --trace writes a loadable Perfetto export with the
    parse/check spans (host backend: no device needed)."""
    from comdb2_tpu.filetest import main as filetest_main
    from comdb2_tpu.ops.history import history_to_edn
    from comdb2_tpu.ops.synth import register_history

    h = register_history(random.Random(6), 3, 16, p_info=0.0)
    edn = tmp_path / "hist.edn"
    edn.write_text(history_to_edn(list(h)))
    out = tmp_path / "trace.json"
    rc = filetest_main([str(edn), "--backend", "host",
                        "--trace", str(out)])
    assert rc == 0
    assert not trace.enabled()            # flag must not leak onward
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"filetest.parse", "linear.analysis",
            "linear.pack"} <= names, names


# --- the wire ----------------------------------------------------------------

def test_daemon_metrics_and_trace_artifacts(tmp_path):
    """End to end: daemon --trace --store, one check, scrape over the
    wire, shutdown writes trace.json + timeline.svg, store web index
    links them."""
    from comdb2_tpu.ops.synth import register_history
    from comdb2_tpu.service.client import ServiceClient

    store = tmp_path / "store"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "comdb2_tpu.service", "--port", "0",
         "--backend", "cpu", "--no-prime", "--frontier", "64",
         "--coalesce-ms", "2", "--trace", "--store", str(store)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=ROOT, env=env)
    try:
        ready = json.loads(proc.stdout.readline())
        assert ready.get("ready") and ready.get("trace"), ready
        c = ServiceClient("127.0.0.1", ready["port"],
                          timeout_s=300.0)
        h = register_history(random.Random(5), 3, 24, p_info=0.0)
        r = c.check(h)
        assert r["ok"] and r.get("stages"), r
        m = c.metrics()
        assert m["ok"] and m["kind"] == "metrics"
        assert m["metrics"]["service_dispatches_total"]["series"][0][
            "value"] >= 1
        st = c.status()["status"]
        assert st["tracing"] is True
        assert c.shutdown()
    finally:
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
            raise
    assert rc == 0
    doc = json.loads((store / "service" / "trace.json").read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"admission", "stage", "device", "finalize",
            "request"} <= names, names
    dev = [e for e in doc["traceEvents"] if e["name"] == "device"]
    assert any(e["args"].get("bytes_h2d", 0) > 0 for e in dev)
    assert (store / "service" / "timeline.svg").exists()
    from comdb2_tpu.harness.web import _index_html

    idx = _index_html(str(store))
    assert "trace.json" in idx and "timeline.svg" in idx
