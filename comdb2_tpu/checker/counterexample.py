"""Bounded counterexample reconstruction for INVALID verdicts.

The role of the reference's ``final-paths`` (``knossos/linear.clj:
180-212``): turn "the frontier died at op i" into concrete failed
linearization orders a human can read. Round 1 re-ran the ENTIRE
history through the host engine to decode counterexamples — on a
50k-op history that resurrects the very CPU path the TPU replaced.

Here the work is bounded:

1. Re-scan the history on device in chunks (the adaptive segmented
   engine, :func:`~.linear_jax.check_device_seg2_chunk`), keeping the
   carry at the last chunk boundary BEFORE the frontier died. The
   carry's ``(states, slots, valid)`` triple decodes directly into
   host configs.
2. Replay at most one chunk of segments on host from that frontier
   (:func:`~.linear_host.check` with ``start_index``/``init_configs``)
   to recover the exact dying op, the closed frontier at death, and
   the pre-closure frontier.
3. DFS the pre-closure frontier's pending-call orders against the
   memoized model graph to produce ``final paths`` — each path is a
   sequence of (op, resulting model state) ending in the step that
   made the model inconsistent.

Device scan cost equals the original check's; host replay touches at
most ``chunk`` segments at frontier width <= F.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Set

import numpy as np

from ..models.memo import MemoizedModel
from ..obs import trace as _obs
from ..ops.packed import PackedHistory
from ..utils import next_pow2 as _next_pow2
from . import linear_host
from .linear_host import IDLE, LIN, Config


@dataclass
class Counterexample:
    op_index: int                      # history index where search died
    configs: List[dict]                # decoded closed frontier at death
    paths: List[list] = field(default_factory=list)  # final paths
    raw_configs: List[Config] = field(default_factory=list)
    replayed_segments: int = 0         # host-replay bound (diagnostics)


def _unmap_configs(cfgs, owners_row, P: int) -> Set[Config]:
    """Map configs decoded in renamed-slot space (see
    :func:`~.linear_jax.remap_slots`) back to process-indexed slots of
    width ``P``. ``owners_row`` is the slot -> original-process map at
    the decoded segment boundary; a non-IDLE slot (pending OR
    linearized-but-not-returned) always has an owner there — the map
    only frees a slot at its ok."""
    out: Set[Config] = set()
    for (st, sl) in cfgs:
        slots = [IDLE] * P
        for q, t in enumerate(sl):
            if t == IDLE:
                continue
            p = int(owners_row[q]) if q < len(owners_row) else -1
            if p < 0:
                raise ValueError(
                    f"occupied slot {q} has no owning process at the "
                    "decoded boundary — owner map out of sync")
            slots[p] = t
        out.add((int(st), tuple(slots)))
    return out


def _carry_configs(carry, P: int) -> Set[Config]:
    """Decode a device seg-scan carry (states, slots, valid, ...) into
    host configs. Slot encoding is shared with the host engine
    (IDLE/LIN/transition-id); padding slots beyond P are always IDLE."""
    states = np.asarray(carry[0])
    slots = np.asarray(carry[1])
    valid = np.asarray(carry[2])
    return {(int(states[i]), tuple(int(x) for x in slots[i][:P]))
            for i in np.flatnonzero(valid)}


def reconstruct(mm: MemoizedModel, packed: PackedHistory,
                F: int = 256, chunk: int = 2048,
                max_paths: int = 10,
                max_host_configs: int = 1 << 16
                ) -> Optional[Counterexample]:
    """Reconstruct the counterexample for a history the device engines
    judged INVALID. Returns None when the re-scan does not reproduce
    the failure (e.g. the verdict came from a different engine setup).
    """
    from . import linear_jax as LJ

    P = len(packed.process_table)
    sizes = {"n_states": mm.n_states, "n_transitions": mm.n_transitions}
    # the same shape buckets AND slot renaming as
    # linear._analyze_device so the re-scan reuses the verdict path's
    # compiled programs instead of compiling fresh ones per raw (S, K).
    # The device frontier decodes in renamed-slot space; ``owners``
    # (slot -> original process, per segment) maps it back before the
    # host replay, which speaks process-indexed slots.
    with _obs.span("linear.segments") as sp:
        segs = LJ.make_segments(packed)
        S = segs.ok_proc.shape[0]
        segs = LJ.make_segments(
            packed, s_pad=_next_pow2(S, 64),
            k_pad=_next_pow2(segs.inv_proc.shape[1], 2))
        segs, P_eff, owners = LJ.remap_slots(segs, with_maps=True)
        sp.set(segments=S, builds=2, p_eff=P_eff)
    Pe = max(P_eff, 1)
    P2 = max(Pe + (Pe & 1), 2)

    # fast path: the fused kernel's chunked scan (~6x the XLA engine)
    # hands back the packed boundary frontier directly
    boundary = _pallas_boundary(mm, segs, P2 if P2 <= 7 else Pe, sizes)
    if boundary is not None:
        raw_cfgs, done, fail_seg = boundary
        boundary_cfgs = _unmap_configs(
            raw_cfgs, owners[done - 1] if done > 0 else (), P)
    else:
        # XLA fallback: chunked seg2 scan, decode the carry
        succ = LJ.pad_succ(mm.succ, _next_pow2(mm.succ.shape[0]),
                           _next_pow2(mm.succ.shape[1]))
        # chunk 2048 matches the progress path's chunking (shared
        # compile) and keeps the scan round-trip count low: every dispatch+
        # readback round-trip is a synchronization point
        chunk = max(_next_pow2(min(chunk, max(S, 1))), 64)
        carry = LJ.init_seg_carry(F, P2)
        done = 0
        fail_seg = -1
        while done < S:
            end = min(done + chunk, S)
            pad = chunk - (end - done)
            ip = np.pad(segs.inv_proc[done:end], ((0, pad), (0, 0)),
                        constant_values=-1)
            it = np.pad(segs.inv_tr[done:end], ((0, pad), (0, 0)))
            op_ = np.pad(segs.ok_proc[done:end], (0, pad),
                         constant_values=-1)
            dp = np.pad(segs.depth[done:end], (0, pad))
            carry2 = LJ.check_device_seg2_chunk(
                succ, ip, it, op_, dp, done, carry, F=F, Fs=32, P=P2,
                **sizes)
            if int(carry2[4]) == LJ.INVALID:
                fail_seg = int(carry2[5])
                break
            if int(carry2[4]) != LJ.VALID:   # UNKNOWN: not decodable
                return None
            carry = carry2
            done = end
        if fail_seg < 0:
            return None
        # on the INVALID break ``carry`` still holds the last boundary
        # BEFORE the failing chunk — one frontier readback here instead
        # of one per chunk (each device->host round-trip blocks the
        # host)
        boundary_cfgs = _unmap_configs(
            _carry_configs(carry, Pe),
            owners[done - 1] if done > 0 else (), P)

    # host replay: from the history row after the boundary's last ok
    start_index = (int(segs.seg_index[done - 1]) + 1) if done > 0 else 0
    r = linear_host.check(mm, packed, max_configs=max_host_configs,
                          start_index=start_index,
                          init_configs=boundary_cfgs)
    if r.valid or r.op_index is None:
        return None                           # replay didn't reproduce
    cfgs = [linear_host.describe_config(mm, packed, c)
            for c in r.configs[:10]]
    paths = final_paths(mm, packed, r.pre_configs, r.op_index,
                        max_paths=max_paths)
    return Counterexample(op_index=r.op_index, configs=cfgs,
                          paths=paths, raw_configs=r.configs[:10],
                          replayed_segments=max(fail_seg - done + 1, 0))


def _pallas_boundary(mm, segs, P_k: int, sizes):
    """Run the fused kernel's chunked scan and return
    ``(boundary_configs, done, fail_seg)``, or None when the kernel
    can't serve this shape / didn't reproduce the INVALID."""
    from . import pallas_seg as PSEG

    if not PSEG.available():
        return None
    r = PSEG.check_device_pallas_chunked(
        mm.succ, segs, P=P_k, return_boundary=True, **sizes)
    if r is None or r[0] != PSEG.INVALID:
        return None
    status, fail_seg, _n, (ws, done) = r
    spec = PSEG.spec_for(sizes["n_states"], sizes["n_transitions"],
                         P_k, PSEG.kernel_K(segs.inv_proc.shape[1]))
    return PSEG.decode_frontier(spec, ws, P_k), done, fail_seg


def _op_desc(packed: PackedHistory, q: int, t: int) -> dict:
    """Human-readable pending call: process + (f, value)."""
    f_id, v_id = packed.transition_table[t]
    return {"process": packed.process_table[q],
            "f": packed.f_table[f_id],
            "value": packed.value_table[v_id]}


def final_paths(mm: MemoizedModel, packed: PackedHistory,
                configs: List[Config], op_index: int,
                max_paths: int = 10) -> List[list]:
    """Concrete failed linearization orders (``linear.clj:180-212``).

    For each seed config (the frontier just before the dying ok's
    closure), walk orders of pending calls through the memoized model
    graph; every branch ends in the step that made the model
    inconsistent. Each path is a list of ``{"op": ..., "model": ...}``
    entries whose last model is ``"inconsistent"``."""
    succ = mm.succ
    paths: List[list] = []

    def dfs(s: int, slots, acc) -> None:
        if len(paths) >= max_paths:
            return
        pend = [q for q, t in enumerate(slots) if t >= 0]
        if not pend:
            # every call linearized yet the config died — only possible
            # for malformed input; record it rather than drop the path
            paths.append(acc + [{"op": "(nothing pending)",
                                 "model": "returning process never "
                                          "linearized"}])
            return
        for q in pend:
            if len(paths) >= max_paths:
                return
            t = slots[q]
            s2 = int(succ[s][t])
            opd = _op_desc(packed, q, t)
            if s2 < 0:
                paths.append(acc + [{"op": opd,
                                     "model": "inconsistent"}])
            else:
                dfs(s2, slots[:q] + (LIN,) + slots[q + 1:],
                    acc + [{"op": opd,
                            "model": mm.states[s2].describe()}])

    ok_p = int(packed.process[op_index])
    for (s, slots) in configs:
        if len(paths) >= max_paths:
            break
        # paths that linearize the returning call and survive would
        # contradict the INVALID verdict, so the DFS only ever emits
        # dead ends; seed with the config's current model state
        dfs(int(s), tuple(slots),
            [{"op": "(state before %r returns)"
                    % (packed.process_table[ok_p],),
              "model": mm.states[int(s)].describe()}])
    return paths[:max_paths]
