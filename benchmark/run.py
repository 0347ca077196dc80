#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration, traffic mix and per-layer metrics by the
names in BENCHMARK.json, makes its inputs from ``--seed``, warms up,
measures for ``--seconds``, holds what the timed path answered against
the plain reference (``reference.py``), and prints one JSON object as
the last line of standard output. Earlier lines break set-up down,
count compiles inside the window, and say what the reference compared.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result: there is no CPU fallback.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _cache_counters():
    """Persistent-cache requests and hits, and backend compiles with
    their seconds, from jax.monitoring."""
    import jax

    c = {"cache_requests": 0, "cache_hits": 0, "backend_compiles": 0,
         "backend_compile_s": 0.0}

    def on_event(event, *a, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            c["cache_requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            c["cache_hits"] += 1

    def on_duration(event, secs, *a, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            c["backend_compiles"] += 1
            c["backend_compile_s"] += secs

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return c


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


class RunView:
    """What a per-layer metric reader may look at."""

    def __init__(self, driver, spans, trace, t0, t1):
        self.driver = driver
        self.spans = spans
        self.trace = trace
        self.t0, self.t1 = t0, t1


def run(args, require_chip: bool = True, root: str = harness.ROOT) -> int:
    bench = harness.load_benchmark(root)
    cell = harness.Cell(bench, args.workload, root)
    phases: dict = {}
    with harness.timed(phases, "backend_init_s"):
        import jax

        devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    used = devices[:cell.chips]
    from comdb2_tpu.utils import compile_guard
    from comdb2_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    counters = _cache_counters()
    driver = harness.load_driver(cell.traffic["driver"]).Driver(
        cell, args.seed, used)
    with harness.timed(phases, "generate_s"):
        driver.generate()
    c0 = dict(counters)
    with harness.timed(phases, "warm_up_s"):
        driver.warm_up(control=args.control)
    setup_s = time.perf_counter() - T_PROCESS
    emit({"line": "setup", "setup_s": setup_s, **phases,
          "compile_cache_dir": os.path.relpath(cache_dir, root)
          if cache_dir.startswith(root) else cache_dir,
          **{k: counters[k] - c0[k] for k in counters}})

    readers = cell.metric_readers() if args.trace else {}
    spans = harness.Spans()
    trace_dir = None
    if args.trace:
        for name, mod in readers.items():
            for group, paths in getattr(mod, "WRAP", {}).items():
                if group not in spans.groups:
                    spans.install(group, paths)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c1 = dict(counters)
    guard = compile_guard.CompileGuard().start()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            driver.window(float(args.seconds))
    finally:
        guard.stop()
        if args.trace:
            jax.profiler.stop_trace()
            spans.uninstall()
    g = guard.counters()
    compiles = {"xla_lowerings": g["xla_lowerings"],
                "mosaic_builds": g["mosaic_builds"],
                "backend_compiles": counters["backend_compiles"]
                - c1["backend_compiles"]}
    emit({"line": "compiles_in_window", **compiles})
    for line in driver.lines():
        emit(line)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": _memory_peak(used)}

    t_w0, t_w1 = driver.t_start, driver.t_end
    driver.release()
    gc.collect()

    trace = None
    if args.trace:
        import trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            trace = trace_reduce.Trace.from_file(path, list(spans.groups))
            if args.keep_trace:
                shutil.copyfile(path, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    refs = driver.references([j for j, _ in driver.answers])
    counts = harness.compare(driver.answers, refs,
                             window_compiles=sum(compiles.values()))
    emit({"line": "reference", "seconds": time.perf_counter() - t_ref,
          "pool_entries_checked": len(refs), **counts})
    correct = harness.is_correct(counts)

    metrics = {}
    if args.trace:
        view = RunView(driver, spans, trace, t_w0, t_w1)
        units = {m["name"]: m["unit"] for m in cell.per_layer}
        for name, mod in readers.items():
            v = mod.read(view)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
        if trace is not None and trace.window() is not None:
            device["busy_s"] = trace.busy_s()
            device["window_s"] = trace.window_s()
    else:
        e2e = driver.end_to_end()
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    checks = harness.checks_block(counts)
    result = {"correct": correct, "attempted": driver.attempted,
              "failed": driver.failed, "metrics": metrics,
              "device": device}
    if trace is not None and trace.window() is not None:
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(f"check correct {correct}", file=sys.stderr, flush=True)
    emit(result)
    return 0


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference, with the read guarantee "
                         "dropped, in the program's place (the control "
                         "that has to come out not correct)")
    ap.add_argument("--keep-trace", metavar="PATH",
                    help="with --trace 1, keep a copy of the .xplane.pb")
    return ap


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    try:
        return run(args)
    except ImportError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
