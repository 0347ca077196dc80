"""Independent-key lifting — the batch axis of the framework.

Mirrors ``jepsen/independent.clj``: a test of one register lifts to a
map of keys to registers by wrapping op values in ``(k, v)`` tuples,
partitioning the history per key, and checking each subhistory with a
base checker (``independent.clj:252-300``).

TPU-native twist: when the base checker is :class:`~.checkers.Linearizable`,
all per-key subhistories are packed against ONE shared memoized model and
checked as a single vmapped (optionally mesh-sharded) device launch
(:mod:`comdb2_tpu.checker.batch`) instead of one JVM ``check`` per key —
this is BASELINE config 5, the per-key data parallelism of SURVEY §2.5
item 5 moved onto the device batch axis.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence

from ..obs import trace as _obs
from ..ops.kv import KVTuple, is_tuple, tuple_
from ..ops.op import Op
from .checkers import Checker, Linearizable, check_safe, merge_valid


def wrap_keyed_history(history: Iterable[Op]) -> List[Op]:
    """Re-tag 2-element tuple values as :class:`KVTuple`. EDN histories
    (e.g. from the C register driver) carry ``[k v]`` vectors with no
    type marker; call this when a history is known to be keyed."""
    out = []
    for op in history:
        v = op.value
        if (isinstance(v, (tuple, list)) and len(v) == 2
                and not isinstance(v, KVTuple)):
            op = op.with_(value=KVTuple(v[0], v[1]))
        out.append(op)
    return out


def history_keys(history: Iterable[Op]) -> List[Any]:
    """Distinct keys in first-appearance order
    (``independent.clj:227-238``)."""
    seen: Dict[Any, None] = {}
    for op in history:
        if is_tuple(op.value):
            seen.setdefault(op.value.key, None)
    return list(seen)


def subhistory(k, history: Iterable[Op]) -> List[Op]:
    """All ops without a differing key, tuples unwrapped — un-keyed ops
    (nemesis infos, logging) appear in every subhistory
    (``independent.clj:240-250``)."""
    out = []
    for op in history:
        v = op.value
        if not is_tuple(v):
            out.append(op)
        elif v.key == k:
            out.append(op.with_(value=v.value))
    return out


def split(history: Sequence[Op]) -> Dict[Any, List[Op]]:
    """Every key's :func:`subhistory`, keys in first-appearance order,
    in ONE pass over the history: keyed ops go to their key, tuples
    unwrapped; an un-keyed op goes to every key, in order — also to
    keys that first appear after it (``independent.clj:240-250``).
    Opens the span ``independent.split`` (``keys``, ``events``)."""
    with _obs.span("independent.split") as sp:
        subs: Dict[Any, List[Op]] = {}
        unkeyed: List[Op] = []
        for op in history:
            v = op.value
            if is_tuple(v):
                sub = subs.get(v[0])
                if sub is None:          # a new key: the un-keyed so far
                    sub = subs[v[0]] = list(unkeyed)
                sub.append(op.with_(value=v[1]))
            else:
                unkeyed.append(op)
                for sub in subs.values():
                    sub.append(op)
        sp.set(keys=len(subs), events=len(history))
    return subs


class IndependentChecker(Checker):
    """Lift a base checker over keyed histories: valid iff valid for
    every key's subhistory; per-key results under ``"results"``, invalid
    keys under ``"failures"`` (``independent.clj:252-300``)."""

    def __init__(self, base: Checker, batch_frontier: int = 256,
                 mesh=None):
        self.base = base
        self.batch_frontier = batch_frontier
        self.mesh = mesh

    def check(self, test, model, history, opts=None):
        import os

        from ..harness.store import artifact_dir

        history = list(history)
        subs = split(history)
        ks = list(subs)
        # per-key artifact routing: a failing base checker writes its
        # counterexample under independent/<k>/ (the reference's per-key
        # store layout) instead of every key clobbering one linear.svg
        base_dir = artifact_dir(test, opts)

        def key_opts(k):
            if base_dir is None:
                return opts
            return {**(opts or {}),
                    "dir": os.path.join(base_dir, "independent", str(k))}

        # honor an explicit host backend: fault-heavy harness histories
        # have retirement-inflated process counts whose one-off device
        # shapes cost minutes of compile for milliseconds of work
        device_ok = not (isinstance(self.base, Linearizable)
                         and getattr(self.base, "backend", None) == "host")
        if isinstance(self.base, Linearizable) and len(ks) > 1 \
                and device_ok:
            results = self._check_linearizable_batch(model, subs,
                                                     key_opts)
        else:
            results = {k: check_safe(self.base, test, model, subs[k],
                                     key_opts(k))
                       for k in ks}
        self._write_artifacts(test, subs, results, opts)
        # false > unknown > true, like compose; only definitively-invalid
        # keys are failures (the reference treats :unknown as truthy,
        # independent.clj:288-295)
        valid = merge_valid([r.get("valid?") for r in results.values()])
        failures = [k for k, r in results.items()
                    if r.get("valid?") is False]
        return {"valid?": valid, "results": results, "failures": failures}

    def _write_artifacts(self, test, subs, results, opts) -> None:
        """Persist per-key results.edn + history.edn under
        ``independent/<k>/`` in the test's store dir when one exists
        (``independent.clj:272-283``); best-effort."""
        import os

        from ..harness.store import artifact_dir

        base = artifact_dir(test, opts)
        if base is None:
            return
        from ..harness.store import _edn_safe
        from ..ops.edn import write_edn
        from ..ops.history import history_to_edn

        try:
            for k, r in results.items():
                d = os.path.join(base, "independent", str(k))
                os.makedirs(d, exist_ok=True)
                with open(os.path.join(d, "results.edn"), "w") as fh:
                    fh.write(write_edn(_edn_safe(r)))
                with open(os.path.join(d, "history.edn"), "w") as fh:
                    fh.write(history_to_edn(subs[k]))
        except Exception:
            # genuinely best-effort: an unserializable payload must not
            # turn an already-computed verdict into :unknown
            pass

    def _check_linearizable_batch(self, model, subs: Dict[Any, List[Op]],
                                  key_opts=lambda k: None
                                  ) -> Dict[Any, dict]:
        """One device launch for all keys; unknowns (frontier overflow)
        and packing failures fall back to the per-key escalating path."""
        from . import batch as B
        from . import linear_jax as LJ

        ks = list(subs)
        try:
            pb = B.pack_batch([subs[k] for k in ks], model)
            status, fail_at, _ = B.check_batch(pb, F=self.batch_frontier,
                                               mesh=self.mesh)
        except Exception:
            return {k: check_safe(self.base, {}, model, subs[k],
                                  key_opts(k))
                    for k in ks}
        results: Dict[Any, dict] = {}
        for i, k in enumerate(ks):
            st = int(status[i])
            if st == LJ.VALID:
                results[k] = {"valid?": True, "backend": "device-batch"}
                continue
            # invalid or overflow: re-check solo for an exact verdict
            # with escalation and a decoded counterexample
            reason = "invalid" if st == LJ.INVALID else "overflow"
            with _obs.span("independent.recheck", key=str(k),
                           reason=reason):
                results[k] = check_safe(self.base, {}, model, subs[k],
                                        key_opts(k))
        return results


def checker(base: Checker, **kw) -> IndependentChecker:
    return IndependentChecker(base, **kw)
