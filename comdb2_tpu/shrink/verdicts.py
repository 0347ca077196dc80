"""Batched candidate verdicts — the device half of the minimizer.

Every shrink round produces B candidate sub-histories; testing them is
exactly the batched-``check_batch`` workload, so this module's job is
to keep the per-candidate device cost at "one lane of one dispatch":

- candidates are grouped into **pow2 kept-op buckets** and each bucket
  chunk rides ONE :func:`~comdb2_tpu.checker.batch.check_batch` call
  (batch axis pow2-padded with copies of the first candidate, table
  sizes pow2-floored to the shared parent memo) — the same
  closed-compiled-program-set discipline as the verifier service;
- candidates with no ok-completion are answered VALID without any
  dispatch (nothing ever constrains the frontier — the service's
  trivial path);
- an engine blowup degrades that chunk to UNKNOWN (a non-survivor:
  the minimizer keeps those ops) instead of killing the whole run.

:func:`check_candidate` is the one-candidate-per-dispatch serial
control — it exists for benchmarks and oracles. Driving it from a
production loop is the exact round-trip-bound bug this subsystem
exists to avoid (one round trip per dispatch), and the
``per-item-dispatch`` analysis rule flags it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..checker import linear_jax as LJ
from ..checker.batch import check_batch, pack_batch_masked
from ..models.memo import MemoizedModel
from ..ops.op import OK
from ..ops.packed import PackedHistory
from ..utils import next_pow2

#: smallest pow2 kept-op bucket — tiny endgame candidates share one
#: shape class instead of compiling per size
MIN_BUCKET = 16

#: candidates per dispatch chunk: big enough to amortize the ~100 ms
#: round-trip over a whole ddmin round, small enough that one chunk's
#: host slicing stays below the device time it overlaps
MAX_BATCH = 64


def bucket_of(n_rows: int) -> int:
    """The pow2 kept-op bucket a candidate lands in (floor
    :data:`MIN_BUCKET`)."""
    return next_pow2(max(int(n_rows), 1), MIN_BUCKET)


def check_candidates(parent: PackedHistory, masks: Sequence[np.ndarray],
                     memo: MemoizedModel, *, F: int = 1024, mesh=None,
                     max_batch: int = MAX_BATCH,
                     counters: Optional[dict] = None) -> np.ndarray:
    """Verdict-test B candidate row masks of one packed parent.

    Returns ``int32[B]`` engine statuses (``LJ.VALID`` / ``INVALID`` /
    ``UNKNOWN``) aligned with ``masks``. ONE ``check_batch`` dispatch
    per pow2 shape-bucket chunk; ``counters`` (optional) accumulates
    ``{"dispatches", "candidates"}``.
    """
    masks = [np.asarray(m, bool) for m in masks]
    out = np.full(len(masks), LJ.VALID, np.int32)
    if counters is not None:
        counters["candidates"] = counters.get("candidates", 0) \
            + len(masks)
    ok_rows = np.asarray(parent.type) == OK
    groups: Dict[int, List[int]] = {}
    for i, m in enumerate(masks):
        if not bool((m & ok_rows).any()):
            continue                    # trivially VALID, no dispatch
        groups.setdefault(bucket_of(int(m.sum())), []).append(i)
    ns = next_pow2(memo.n_states)
    nt = next_pow2(memo.n_transitions)
    for _, idxs in sorted(groups.items()):
        for lo in range(0, len(idxs), max_batch):
            chunk = idxs[lo:lo + max_batch]
            cand = [masks[i] for i in chunk]
            b = next_pow2(len(cand))
            cand = cand + [cand[0]] * (b - len(cand))
            try:
                batch = pack_batch_masked(parent, cand, memo)
                status, _, _ = check_batch(
                    batch, F=F, mesh=mesh,
                    n_states_pad=ns, n_transitions_pad=nt)
                out[chunk] = status[:len(chunk)]
            except Exception:           # noqa: BLE001 — engine blowup
                # a candidate shape the engines can't serve is a
                # non-survivor, never a crashed minimization
                out[chunk] = LJ.UNKNOWN
            if counters is not None:
                counters["dispatches"] = counters.get("dispatches",
                                                      0) + 1
    return out


def check_candidate(parent: PackedHistory, mask: np.ndarray,
                    memo: MemoizedModel, **kw) -> int:
    """ONE candidate, one dispatch — the serial control the batched
    path exists to beat. Production code must batch a round's candidates through
    :func:`check_candidates` instead; the ``per-item-dispatch``
    analysis rule flags loops over this entry point."""
    return int(check_candidates(parent, [mask], memo, **kw)[0])


__all__ = ["MAX_BATCH", "MIN_BUCKET", "bucket_of", "check_candidate",
           "check_candidates"]
