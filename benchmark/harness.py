"""What every cell shares: finding a cell's files by name, the exact
statistics, the benchmark-side spans, and the comparison with the
reference. Nothing here imports JAX; the program is imported only by
the drivers and by ``spans.install``.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# -- finding things by name --------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, name: Optional[str] = None):
    """Import one file of the benchmark by path (metric files carry dots
    in their names, so they are not importable as modules)."""
    name = name or "bench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with its configuration, traffic mix and
    per-layer metrics, all found by the names in BENCHMARK.json."""

    def __init__(self, bench: dict, workload: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = cfgs[self.entry["config"]]
        self.config = load_json(os.path.join(root,
                                             self.config_entry["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(os.path.join(
            root, "benchmark", "traffic", self.traffic_name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]

    def metric_readers(self) -> Dict[str, object]:
        return {m["name"]: load_module(
                    os.path.join(HERE, "metrics", m["name"] + ".py"))
                for m in self.per_layer}


def load_driver(name: str):
    return load_module(os.path.join(HERE, "drivers", name + ".py"),
                       "bench_driver_" + name)


# -- exact statistics ----------------------------------------------------

def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The nearest-rank ``q``-th percentile (0 < q <= 100) of all
    values: the smallest sample with at least q% of the samples at or
    below it. Exact; no interpolation, no buckets."""
    if not values:
        return None
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def rate(count: float, t0: float, t1: float) -> Optional[float]:
    """``count`` per second over [t0, t1]."""
    if t1 <= t0:
        return None
    return count / (t1 - t0)


# -- benchmark-side spans ------------------------------------------------

class Spans:
    """Host intervals per group, recorded by wrappers installed from the
    benchmark's own files around program functions. Each wrapper also
    opens a ``jax.profiler.TraceAnnotation`` of the group's name, so
    the device trace can say what the host did in a device gap."""

    def __init__(self):
        self.groups: Dict[str, List[Tuple[float, float]]] = {}
        self.missing: Dict[str, List[str]] = {}
        self._undo: List[Tuple[object, str, object]] = []
        self._lock = threading.Lock()

    def install(self, group: str, dotted: Sequence[str]) -> None:
        """Wrap each ``module.attr`` (or ``module.Class.attr``); a path
        that no longer resolves is listed under ``missing``."""
        from jax.profiler import TraceAnnotation

        self.groups.setdefault(group, [])
        for path in dotted:
            owner, attr = _resolve(path)
            if owner is None:
                self.missing.setdefault(group, []).append(path)
                continue
            orig = getattr(owner, attr)
            rec = self.groups[group]
            lock = self._lock

            def wrapped(*a, __orig=orig, __group=group, __rec=rec, **kw):
                t0 = time.perf_counter()
                try:
                    with TraceAnnotation(__group):
                        return __orig(*a, **kw)
                finally:
                    with lock:
                        __rec.append((t0, time.perf_counter()))

            wrapped.__wrapped__ = orig
            setattr(owner, attr, wrapped)
            self._undo.append((owner, attr, orig))

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()

    def share(self, group: str, t0: float, t1: float) -> Optional[float]:
        """Percent of [t0, t1] covered by the group's spans; None when
        any of its wrapped functions no longer exists."""
        if group not in self.groups or self.missing.get(group):
            return None
        busy = trace_reduce.union(self.groups[group], t0, t1)
        return 100.0 * sum(b - a for a, b in busy) / (t1 - t0)


def _resolve(path: str):
    parts = path.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:cut]))
        except ImportError:
            continue
        for p in parts[cut:-1]:
            obj = getattr(obj, p, None)
            if obj is None:
                return None, None
        return (obj, parts[-1]) if hasattr(obj, parts[-1]) else (None, None)
    return None, None


# -- comparison with the reference ---------------------------------------

#: the numbers compared and their limits; every one is an exact count
LIMITS = {"verdict_mismatches": 0, "fail_index_mismatches": 0,
          "unknown_verdicts": 0, "unanswered": 0, "window_compiles": 0}


def compare(answers: Sequence[Tuple[int, Optional[dict]]],
            refs: Dict[int, dict],
            window_compiles: int = 0) -> Dict[str, int]:
    """Hold each answer ``(pool index, {key: (valid, fail_index)})``
    against the reference's answer for that pool entry. The reference
    always answers exactly, so an ``unknown`` verdict is an answer it
    does not give: ``unknown_verdicts``. A missing answer or key is
    ``unanswered``. ``window_compiles`` (programs lowered or compiled
    inside the window) is carried along: a run that compiled there did
    not measure what its numbers say."""
    out = {k: 0 for k in LIMITS}
    out["window_compiles"] = int(window_compiles)
    out["compared"] = 0
    for j, ans in answers:
        ref = refs[j]
        for key, (rv, ri) in ref.items():
            got = None if ans is None else ans.get(key)
            if got is None:
                out["unanswered"] += 1
                continue
            out["compared"] += 1
            v, i = got
            if v is not True and v is not False:
                out["unknown_verdicts"] += 1
            elif v is not rv:
                out["verdict_mismatches"] += 1
            elif v is False and i != ri:
                out["fail_index_mismatches"] += 1
    return out


def checks_block(counts: Dict[str, int]) -> Dict[str, dict]:
    return {k: {"value": counts[k], "limit": LIMITS[k]} for k in LIMITS}


def is_correct(counts: Dict[str, int]) -> bool:
    return counts.get("compared", 0) > 0 and all(
        counts[k] <= lim for k, lim in LIMITS.items())


@contextlib.contextmanager
def timed(store: dict, key: str):
    t0 = time.perf_counter()
    try:
        yield
    finally:
        store[key] = store.get(key, 0.0) + time.perf_counter() - t0
