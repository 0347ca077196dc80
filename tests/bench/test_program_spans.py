"""The checker's own layer spans and the reduction that attributes the
device's idle time to them: the spans a check opens on the CPU, the
attribution on hand-made events, and both on a small trace recorded
on the chip (``fixtures/chip_trace_spans.xplane.pb``: a traced 2 s
window of ``register-50k.posthoc`` on one v5e, the twin and two plain
histories; to keep it small, its host plane holds only the window, the
benchmark's wrapper spans and the program spans, and the HLO metadata
plane was dropped)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import gen  # noqa: E402
import program_spans as PSP  # noqa: E402
import trace_reduce as TR  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "chip_trace_spans.xplane.pb")


@pytest.fixture
def kernel_path(request):
    """``"kernel"`` runs the fused kernel (interpret mode), as the chip
    does; ``"xla"`` the XLA engines, as the CPU does by default."""
    from comdb2_tpu.checker import pallas_seg as PS

    on = request.param == "kernel"
    PS.use_interpret(on)
    PS.available.cache_clear()
    PS.available()                # the probe's own spans stay out
    try:
        yield on
    finally:
        PS.use_interpret(False)
        PS.available.cache_clear()


def _checked_spans(history):
    from comdb2_tpu.checker.checkers import Linearizable
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.obs import trace
    from comdb2_tpu.ops.op import Op

    trace.clear()
    trace.enable()
    try:
        r = Linearizable().check({}, cas_register(),
                                 [Op(*op) for op in history])
        return r["valid?"], trace.spans()
    finally:
        trace.disable()
        trace.clear()


@pytest.mark.parametrize("kernel_path", ["xla", "kernel"], indirect=True)
def test_a_check_opens_one_span_per_layer(kernel_path):
    plain = gen.register_history(gen.rng_for(2**31 + 3, 0), 5, 400, 5,
                                 fs=("read", "write", "cas"))
    twin, _ = gen.corrupt(plain, 0.5)
    for history, twinned in ((plain, False), (twin, True)):
        valid, spans = _checked_spans(history)
        assert valid is (not twinned)
        names = [s.name for s in spans]
        by = {n: [s for s in spans if s.name == n] for n in names}
        assert len(by["linear.pack"]) == 1
        assert by["linear.pack"][0].args["n"] == len(history)
        segs = by["linear.segments"]
        assert sum(s.args["builds"] for s in segs) == (4 if twinned
                                                       else 2)
        assert all(s.args["segments"] > 0 and s.args["p_eff"] >= 1
                   for s in segs)
        assert names.count("linear.counterexample") == int(twinned)
        if kernel_path:
            # the verdict's scan, and on the twin the re-scan chunk by
            # chunk: one dispatch and one read per chunk, and one read
            # of the boundary frontier
            scans = by["pallas.scan"]
            assert len(by["pallas.prepare"]) == len(scans) == (
                2 if twinned else 1)
            assert scans[0].args == {"dispatches": 1, "readbacks": 1}
            if twinned:
                a = scans[1].args
                assert a["readbacks"] == a["dispatches"] + 1 >= 2
            assert "linear.escalate" not in names
        else:
            assert "pallas.scan" not in names
            esc = by["linear.escalate"]
            assert [s.args["engine"] for s in esc] == ["xla-seg2"]
            assert esc[0].args["capacity"] == 256
        if twinned:
            ce = by["linear.counterexample"][0]
            assert ce.t0 <= segs[1].t0 and segs[1].t1 <= ce.t1
        c = PSP.counts(spans)
        assert c["histories"] == 1
        assert c["segment_builds"] == (4 if twinned else 2)
        assert c["counterexamples"] == int(twinned)
        assert c["escalations"] == (0 if kernel_path else 1)


def _trace():
    """Device 0 busy on [0,1], [4,5], [8,8.2], [9,10]; device 1 on
    [0,10]."""
    host = {TR.WINDOW_SPAN: [(0.0, 10.0)],
            "linear.pack": [(1.0, 2.0)],
            "linear.segments": [(1.5, 3.5)],
            "pallas.prepare": [(3.5, 3.8)],
            "pallas.scan": [(3.8, 5.2), (7.8, 8.5)],
            "linear.counterexample": [(6.0, 8.5)],
            "linear.escalate": [(5.2, 5.6)],
            "segment": [(1.5, 3.5)]}
    dev = {0: [("%k = custom-call(), custom_call_target="
                '"tpu_custom_call"', 0.0, 1.0),
               ("%k = custom-call(), custom_call_target="
                '"tpu_custom_call"', 4.0, 5.0),
               ("%k = custom-call(), custom_call_target="
                '"tpu_custom_call"', 8.0, 8.2),
               ("fusion", 9.0, 10.0)],
           1: [("fusion", 0.0, 10.0)]}
    return TR.Trace(host, dev)


def test_idle_is_attributed_once_in_span_order():
    t = _trace()
    sh = PSP.idle_shares(t)
    # device 0 idles 6.8 s of 10. [1,4]: pack 1 (1-2), segments 1.5
    # (2-3.5, not 1.5-2 again), prepare 0.3, scan 0.2. [5,8]: scan
    # 0.2 (5-5.2), escalate 0.4, nothing 0.4 (5.6-6), counterexample
    # 2 (6-8, over the scan from 7.8). [8.2,9]: counterexample 0.3,
    # nothing 0.5. Device 1 never idles: each share is half device 0's.
    want = {"ingest": 1.0, "segment": 1.5, "dispatch": 0.7,
            "escalation": 0.4, "counterexample": 2.3,
            "unattributed": 0.9}
    assert set(sh) == set(want)
    for g, s in want.items():
        assert sh[g] == pytest.approx(100 * s / 2 / 10), g
    assert sum(sh.values()) == pytest.approx(t.idle_share())
    gaps = PSP.idle_gaps(t)
    assert [g[0] for g in gaps] == ["linear.segments",
                                    "linear.counterexample",
                                    "unattributed"]
    assert [g[1] for g in gaps] == pytest.approx([3.0, 3.0, 0.8])


def test_fused_kernel_time_leaves_the_rescan_out():
    # the kernel at [4,5] runs under pallas.scan; [0,1] under no scan
    # and [8,8.2] under the counterexample's re-scan
    assert PSP.fused_kernel_s(_trace()) == pytest.approx(1.0)


def test_no_program_span_reads_nothing():
    host = {TR.WINDOW_SPAN: [(0.0, 10.0)], "segment": [(1.0, 2.0)]}
    t = TR.Trace(host, {0: [("fusion", 0.0, 1.0)]})
    assert PSP.idle_shares(t) is None and PSP.idle_gaps(t) == []
    assert PSP.fused_kernel_s(t) is None
    assert PSP.counts([]) is None


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no chip trace fixture with program spans")
def test_chip_trace_fixture_with_program_spans():
    from jax.profiler import ProfileData

    modules = {e.name.split("(")[0]
               for p in ProfileData.from_file(FIXTURE).planes
               if p.name.startswith("/device:TPU:")
               for ln in p.lines if ln.name == "XLA Modules"
               for e in ln.events}
    assert "jit_seg_scan" in modules, modules
    t = TR.Trace.from_file(FIXTURE, PSP.ORDER)
    assert {"linear.pack", "linear.segments", "pallas.scan",
            "linear.counterexample"} <= set(t.host_spans)
    sh = PSP.idle_shares(t)
    assert sum(sh.values()) == pytest.approx(t.idle_share(), abs=0.1)
    assert sh["segment"] > 0 and sh["counterexample"] > 0
    fused = PSP.fused_kernel_s(t)
    assert 0 < fused < t.op_seconds(TR.is_kernel)
