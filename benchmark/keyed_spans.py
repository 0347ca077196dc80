"""The program's spans in a traced run of a keyed cell, and what they
say about the device's idle time.

``independent.checker`` opens ``independent.split`` (the one-pass key
split) and ``independent.recheck`` (a key checked again on its own,
``reason`` = ``invalid`` or ``overflow``); the batch under it opens
``batch.pack``, ``batch.remap`` / ``batch.segments`` (segments and
slot renaming), ``batch.dispatch`` and ``batch.collect`` (whose args
carry the kernel's per-rung segment counters). With the profiler sink
on, each span lands on the profiler's host plane, on the clock of the
device ops. Every device-idle instant of the window counts once, under
the first group in :data:`ORDER` whose span is open at it — recheck,
split, stream build, dispatch — or as unattributed.

A metric file that reads these imports this module, calls
:func:`start` as it is loaded and declares ``WRAP = WRAP`` (the span
names the harness loads from the trace). A program without these
spans leaves every such metric without a reading.
"""

from __future__ import annotations

import functools
import inspect
import json
from typing import Dict, Optional

import trace_reduce
from program_spans import _intersect, _length, _subtract

Interval = trace_reduce.Interval

#: the groups, in the order they claim a device-idle instant, and the
#: program spans of each
ORDER = (("recheck", ("independent.recheck",)),
         ("split", ("independent.split", "batch.pack")),
         ("stream_build", ("batch.segments", "batch.remap")),
         ("dispatch", ("batch.dispatch", "batch.collect",
                       "batch.finalize")))
UNATTRIBUTED = "unattributed"
SPAN_NAMES = tuple(n for _, names in ORDER for n in names)
WRAP: Dict[str, list] = {name: [] for name in SPAN_NAMES}
#: the fused kernel's device op (its ``pallas_call`` name)
KERNEL = "seg_frontier"

_started = False


def start() -> None:
    """Clear the program's span buffer and turn its tracing on, with
    ``jax.profiler.TraceAnnotation`` as the sink. A program whose
    ``enable`` takes no sink is left as it is."""
    global _started
    from comdb2_tpu.obs import trace

    if "annotate" not in inspect.signature(trace.enable).parameters:
        return
    import jax

    trace.clear()
    trace.enable(annotate=jax.profiler.TraceAnnotation)
    _started = True


def is_seg_frontier(hlo: str) -> bool:
    return trace_reduce.is_kernel(hlo) and \
        KERNEL in hlo.split(" = ", 1)[0]


def kernel_s(tr: trace_reduce.Trace) -> Optional[float]:
    """Device seconds of the fused kernel's calls inside the window,
    summed over devices; None without one."""
    if tr is None or tr.window() is None:
        return None
    s = tr.op_seconds(is_seg_frontier)
    return s if s > 0.0 else None


def idle_shares(tr: trace_reduce.Trace) -> Optional[Dict[str, float]]:
    """Percent of the window the device sat idle under each group (and
    unattributed), mean over devices: they sum to ``idle_share()``.
    None without a window, a device plane or any of the spans."""
    w = tr.window()
    if (w is None or not tr.device_ops
            or not any(n in tr.host_spans for n in SPAN_NAMES)):
        return None
    lo, hi = w
    spans = {g: trace_reduce.union(
                 [iv for n in names for iv in tr.host_spans.get(n, [])],
                 lo, hi)
             for g, names in ORDER}
    out = dict.fromkeys([g for g, _ in ORDER] + [UNATTRIBUTED], 0.0)
    for d in tr.device_ops:
        left = _subtract([(lo, hi)], tr.busy(d))
        for g, _ in ORDER:
            out[g] += _length(_intersect(left, spans[g]))
            left = _subtract(left, spans[g])
        out[UNATTRIBUTED] += _length(left)
    scale = 100.0 / (len(tr.device_ops) * (hi - lo))
    return {g: s * scale for g, s in out.items()}


def counts(spans) -> Optional[dict]:
    """Per keyed history checked (one ``independent.split`` each): its
    keys, keys checked again by reason, and segments per wide rung and
    past the top rung — on the batch's stream path (``batch.collect``)
    and in the checks again (``pallas.rungs``)."""
    hist = [s for s in spans if s.name == "independent.split"]
    if not hist:
        return None
    n = len(hist)

    def rungs(name):
        tot, past = None, 0
        for s in spans:
            if s.name != name or "rung_segments" not in s.args:
                continue
            r = s.args["rung_segments"]
            tot = list(r) if tot is None else [a + b
                                               for a, b in zip(tot, r)]
            past += s.args.get("past_top", 0)
        return ([x / n for x in tot] if tot is not None else None,
                past / n)

    seg, past = rungs("batch.collect")
    rseg, rpast = rungs("pallas.rungs")
    rechecks = [s.args.get("reason") for s in spans
                if s.name == "independent.recheck"]
    return {"histories": n,
            "keys": sum(s.args.get("keys", 0) for s in hist) / n,
            "rechecked_invalid": rechecks.count("invalid") / n,
            "rechecked_overflow": rechecks.count("overflow") / n,
            "segments_per_rung": seg, "past_top": past,
            "recheck_segments_per_rung": rseg,
            "recheck_past_top": rpast}


@functools.lru_cache(maxsize=1)
def report(view) -> Optional[dict]:
    """Once per run: turn the program's tracing off, print the
    ``keyed_counts`` and ``keyed_idle`` lines, and give what the
    metric files read."""
    global _started
    if not _started:
        return None
    from comdb2_tpu.obs import trace

    finished = trace.spans()
    trace.disable()
    trace.clear()
    _started = False
    c = counts(finished)
    if c is not None:
        print(json.dumps({"line": "keyed_counts", **c}), flush=True)
    tr = view.trace
    shares = None if tr is None else idle_shares(tr)
    if shares is not None:
        print(json.dumps({"line": "keyed_idle", "shares": shares,
                          "sum": sum(shares.values()),
                          "device_idle": tr.idle_share()}), flush=True)
    return {"shares": shares}


def idle_share(view, group: str) -> Optional[float]:
    r = report(view)
    return None if r is None or r["shares"] is None \
        else r["shares"][group]
