"""The program's own spans in a traced run, and what they say about
the device's idle time.

The checker opens one ``obs.trace`` span per history at each layer
boundary (``linear.pack``, ``linear.segments``, ``pallas.prepare``,
``pallas.scan``, ``linear.escalate``, ``linear.counterexample``).
With the profiler sink on, each span also lands on the profiler's host
plane, on the clock of the device ops. Every device-idle instant of the
window then counts once, under the first span in :data:`ORDER` that is
open at it, or as unattributed.

A metric file that reads these imports this module, calls
:func:`start` as it is loaded and declares ``WRAP = WRAP``. The
harness loads metric files only in a ``--trace 1`` run, after warm-up
and before the profiler starts, so tracing is on for the window alone.
A ``WRAP`` group with no function to wrap only names a host span that
the harness then loads from the trace. A program without the sink, or
without these spans, leaves every such metric without a reading.
"""

from __future__ import annotations

import functools
import inspect
import json
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

Interval = Tuple[float, float]

#: the program's spans, in the order they claim a device-idle instant
ORDER = ("linear.counterexample", "linear.pack", "linear.segments",
         "pallas.prepare", "pallas.scan", "linear.escalate")
#: the layer each span's idle time is reported under
GROUP = {"linear.counterexample": "counterexample",
         "linear.pack": "ingest", "linear.segments": "segment",
         "pallas.prepare": "dispatch", "pallas.scan": "dispatch",
         "linear.escalate": "escalation"}
UNATTRIBUTED = "unattributed"
WRAP: Dict[str, list] = {name: [] for name in ORDER}

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


# -- interval arithmetic on sorted disjoint lists ------------------------

def _length(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)


def _intersect(x: Sequence[Interval], y: Sequence[Interval]
               ) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(x: Sequence[Interval], y: Sequence[Interval]
              ) -> List[Interval]:
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > a:
                out.append((a, y[k][0]))
            a = max(a, y[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


# -- the reduction ----------------------------------------------------------

def _spans(tr: trace_reduce.Trace) -> Optional[Dict[str, List[Interval]]]:
    """Each program span's union inside the window; None without a
    window, a device plane or any program span."""
    w = tr.window()
    if (w is None or not tr.device_ops
            or not any(n in tr.host_spans for n in ORDER)):
        return None
    return {n: trace_reduce.union(tr.host_spans.get(n, []), *w)
            for n in ORDER}


def _claim(gaps: Sequence[Interval], spans: Dict[str, List[Interval]]
           ) -> Dict[str, float]:
    """Seconds of ``gaps`` claimed by each span in ORDER, first come
    first served, and the rest under UNATTRIBUTED."""
    out = {}
    left = list(gaps)
    for n in ORDER:
        out[n] = _length(_intersect(left, spans[n]))
        left = _subtract(left, spans[n])
    out[UNATTRIBUTED] = _length(left)
    return out


def idle_shares(tr: trace_reduce.Trace) -> Optional[Dict[str, float]]:
    """Percent of the window the device sat idle under each group (and
    unattributed), mean over devices: they sum to ``idle_share()``."""
    spans = _spans(tr)
    if spans is None:
        return None
    lo, hi = tr.window()
    out = dict.fromkeys(list(dict.fromkeys(GROUP.values()))
                        + [UNATTRIBUTED], 0.0)
    for d in tr.device_ops:
        for n, s in _claim(_subtract([(lo, hi)], tr.busy(d)),
                           spans).items():
            out[GROUP.get(n, n)] += s
    scale = 100.0 / (len(tr.device_ops) * (hi - lo))
    return {g: s * scale for g, s in out.items()}


def idle_gaps(tr: trace_reduce.Trace, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the first device, each named by
    the program span that claims most of it."""
    spans = _spans(tr)
    if spans is None:
        return []
    lo, hi = tr.window()
    gaps = sorted(_subtract([(lo, hi)], tr.busy(min(tr.device_ops))),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for g in gaps:
        claim = _claim([g], spans)
        out.append([max(claim, key=claim.get), g[1] - g[0]])
    return out


def fused_kernel_s(tr: trace_reduce.Trace) -> Optional[float]:
    """Device seconds of Pallas kernel ops run under ``pallas.scan``
    and outside ``linear.counterexample``: the analysis path's scan,
    without the counterexample's re-scan. Summed over devices."""
    spans = _spans(tr)
    if spans is None or not spans["pallas.scan"]:
        return None
    lo, hi = tr.window()
    where = _subtract(spans["pallas.scan"],
                      spans["linear.counterexample"])
    return sum(_length(_intersect(
        trace_reduce.union([(a, b) for name, a, b in evs
                            if trace_reduce.is_kernel(name)], lo, hi),
        where)) for evs in tr.device_ops.values())


def counts(spans) -> Optional[dict]:
    """Per history checked (one ``linear.pack`` each): segment builds,
    kernel dispatches and readbacks, escalations and counterexamples,
    summed from the finished spans' names and args."""
    hist = sum(1 for s in spans if s.name == "linear.pack")
    if not hist:
        return None

    def total(name, arg=None):
        return sum(s.args.get(arg, 0) if arg else 1
                   for s in spans if s.name == name)

    per = {"segment_builds": total("linear.segments", "builds"),
           "dispatches": total("pallas.scan", "dispatches"),
           "readbacks": total("pallas.scan", "readbacks"),
           "escalations": total("linear.escalate"),
           "counterexamples": total("linear.counterexample")}
    return {"histories": hist, **{k: v / hist for k, v in per.items()}}


@functools.lru_cache(maxsize=1)
def report(view) -> Optional[dict]:
    """Once per run: turn the program's tracing off, print the
    ``program_counts`` and ``program_idle_gaps`` lines, and give what
    the metric files read."""
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
        print(json.dumps({"line": "program_counts", **c}), flush=True)
    tr = view.trace
    shares = None if tr is None else idle_shares(tr)
    if shares is not None:
        print(json.dumps({"line": "program_idle_gaps",
                          "gaps": idle_gaps(tr), "shares": shares}),
              flush=True)
    return {"shares": shares,
            "fused_kernel_s": None if tr is None else fused_kernel_s(tr)}


def idle_share(view, group: str) -> Optional[float]:
    r = report(view)
    return None if r is None or r["shares"] is None \
        else r["shares"][group]
