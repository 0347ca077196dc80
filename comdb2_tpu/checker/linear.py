"""Unified linearizability analysis — the ``knossos.linear/analysis``
equivalent (``linear.clj:299-355``).

Pipeline (mirroring the reference's): ``complete`` → ``index`` → pack →
``memo`` → frontier search → decoded verdict. Small histories run on the
host engine (the analog of staying single-threaded below the reference's
128-config pmap threshold, ``linear.clj:214-216``); larger ones run the
device engine with escalating frontier capacity, where overflow at the
largest capacity yields ``:unknown`` exactly like the reference's
low-memory abort (``linear.clj:318-326``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Union

from ..models.memo import MemoOverflow, MemoizedModel, memo as make_memo
from ..models.model import Model
from ..obs import trace as _obs
from ..ops.op import Op
from ..ops.packed import PackedHistory, pack_history
from ..utils import next_pow2 as _next_pow2
from . import linear_host

UNKNOWN = "unknown"


@dataclass
class Analysis:
    """Checker verdict. ``valid`` is ``True``, ``False``, or
    ``"unknown"`` (search gave up — same tri-state as the reference's
    ``:valid?``)."""

    valid: Union[bool, str]
    op: Optional[Op] = None            # op at which the search died
    op_index: Optional[int] = None
    configs: List[dict] = field(default_factory=list)  # frontier sample
    final_count: int = 0
    info: dict = field(default_factory=dict)

    def to_map(self) -> dict:
        m = {"valid?": self.valid}
        if self.op is not None:
            m["op"] = self.op
            m["op-index"] = self.op_index
            m["configs"] = self.configs
        m.update(self.info)
        return m


# histories with more padded segments than this always run the chunked
# engine (XLA compile time scales with scan length; see _analyze_device)
CHUNKED_S_THRESHOLD = 4096


@_obs.traced("linear.analysis")
def analysis(model: Model,
             history: Union[Sequence[Op], PackedHistory],
             backend: str = "auto",
             capacities: Sequence[int] = (256, 1024, 8192, 65536),
             host_threshold: int = 128,
             max_states: int = 1 << 20,
             max_host_configs: int = 1 << 22,
             progress=None,
             progress_interval_s: float = 5.0) -> Analysis:
    """Check ``history`` against ``model`` for linearizability.

    backend: "auto" | "host" | "device".
    capacities: device frontier sizes tried in order; overflow escalates,
    overflow at the last yields :unknown. The MXU arm (wide P) buckets
    each entry up to its own pow2 rung set (``mxu.CAPACITIES``) so its
    program surface stays closed — the ladder still starts and stops
    where the caller's bounds say.
    progress: optional callback ``progress(done_segments, total_segments,
    frontier_count, stats)`` invoked between device chunks at roughly
    ``progress_interval_s`` cadence — the role of the reference's
    5-second reporter threads (``linear.clj:273-297``). ``stats`` is a
    dict with ``visited_per_s`` (configs stepped per second),
    ``segs_per_s``, and ``est_cost`` (the Σ n·n! pending-count cost
    model of ``linear/config.clj:374-393``). When given, the device
    path runs chunked.
    """
    t0 = _obs.monotonic()
    with _obs.span("linear.pack") as sp:
        packed = (history if isinstance(history, PackedHistory)
                  else pack_history(list(history)))
        n = len(packed)
        P = len(packed.process_table)
        sp.set(n=n, P=P)
        if n == 0 or P == 0:
            return Analysis(valid=True, info={"backend": "trivial"})
        try:
            mm = make_memo(model, packed, max_states=max_states)
        except MemoOverflow as e:
            return Analysis(valid=UNKNOWN, info={"cause": str(e)})

    if backend == "host" or (backend == "auto" and n < host_threshold):
        return _analyze_host(mm, packed, max_host_configs, t0)
    return _analyze_device(mm, packed, capacities, t0,
                           progress=progress,
                           progress_interval_s=progress_interval_s)


@_obs.traced("linear.host")
def _analyze_host(mm: MemoizedModel, packed: PackedHistory,
                  max_configs: int, t0: float) -> Analysis:
    try:
        r = linear_host.check(mm, packed, max_configs=max_configs)
    except linear_host.FrontierOverflow as e:
        return Analysis(valid=UNKNOWN, info={"cause": str(e),
                                             "backend": "host"})
    info = {"backend": "host", "max_frontier": r.max_frontier,
            "time_s": _obs.monotonic() - t0}
    if r.valid:
        return Analysis(valid=True, final_count=r.final_count, info=info)
    op = packed.ops[r.op_index]
    cfgs = [linear_host.describe_config(mm, packed, c)
            for c in r.configs[:10]]
    # final paths on the host path too — the reference's analysis
    # always carries them on invalid (linear.clj:251-265); without
    # this, small (below-host-threshold) histories rendered
    # counterexample SVGs with no linearization orders at all
    try:
        from .counterexample import final_paths
        info["paths"] = final_paths(mm, packed, r.pre_configs,
                                    r.op_index)
    except Exception as e:
        # decoration never destroys the verdict — but a silently
        # dropped decoration is how the no-orders-in-SVG bug hid;
        # leave a diagnosable trace in the report
        info["paths_error"] = repr(e)
    return Analysis(valid=False, op=op, op_index=r.op_index,
                    configs=cfgs, info=info)


@_obs.traced("linear.device")
def _analyze_device(mm: MemoizedModel, packed: PackedHistory,
                    capacities: Sequence[int], t0: float,
                    progress=None,
                    progress_interval_s: float = 5.0) -> Analysis:
    import numpy as np

    from . import linear_jax as LJ

    import jax

    P = len(packed.process_table)
    # ship the successor table once — chunked runs and capacity
    # escalation reuse the same device buffer
    succ = jax.device_put(LJ.pad_succ(mm.succ,
                                      _next_pow2(mm.succ.shape[0]),
                                      _next_pow2(mm.succ.shape[1])))
    with _obs.span("linear.segments") as sp:
        segs = LJ.make_segments(packed)
        s_real = segs.ok_proc.shape[0]
        segs = LJ.make_segments(
            packed, s_pad=_next_pow2(s_real, 64),
            k_pad=_next_pow2(segs.inv_proc.shape[1], 2))
        # slot renaming: processes map to a minimal pool of reusable
        # slots, so every engine's slot axis scales with the history's
        # max CONCURRENT open calls instead of its process count (a
        # concurrency-10 register history with <=6 calls in flight
        # runs the fused kernel's fast (8,128)/2-word tier; a
        # 30-process cluster history with bounded in-flight depth
        # becomes kernel-eligible at all). Pure relabeling — verdicts
        # and fail segments are unchanged (see LJ.remap_slots).
        segs, P_eff = LJ.remap_slots(segs)
        sp.set(segments=s_real, builds=2, p_eff=P_eff)
    P = max(P_eff, 1)
    info: dict = {"backend": "device", "n_states": mm.n_states,
                  "n_transitions": mm.n_transitions,
                  "effective_slots": P}
    sizes = {"n_states": mm.n_states, "n_transitions": mm.n_transitions}
    # bucket the slot axis to the next even value, not pow2: candidate
    # rows scale with P, so pow2 padding costs up to ~25% extra work
    # per closure iteration (measured 9.5k -> 11.4k ops/s on the bench
    # shape); even-bucketing keeps recompiles bounded
    P2 = P + (P & 1)
    P2 = max(P2, 2)
    # fused-kernel fast path: the whole segment loop runs inside one
    # Pallas kernel per 1024-segment chunk (checker/pallas_seg.py),
    # ~4x the XLA engines on a real TPU. Its frontier rungs run from
    # 128 to F_TOP per segment inside the kernel; overflow past the
    # top rung (UNKNOWN) falls through to the XLA capacity ladder, and
    # so do shapes the kernel can't take (key budget, table size,
    # P > 15) — check_device_pallas* return None when spec_for rejects
    # the shape. Off the TPU the kernel is unavailable by design; on
    # it, a failing probe raises instead of demoting to XLA.
    from . import pallas_seg as PSEG

    # even-bucket the kernel's slot count only while it stays in the
    # (8,128) tier; the (16,128) tier keys are wide enough that a pad
    # slot can cost a whole extra key word
    P_k = P2 if P2 <= PSEG.ROWS - 1 else P
    r = None
    # available() probes Mosaic support once per process; past that
    # gate, errors are real bugs (or a raising progress callback) and
    # must propagate, not silently rerun on the XLA path
    if P_k <= 2 * PSEG.ROWS - 1 and PSEG.available():
        if progress is None:
            r = PSEG.check_device_pallas(
                mm.succ, segs, n_states=mm.n_states,
                n_transitions=mm.n_transitions, P=P_k)
        else:
            r = PSEG.check_device_pallas_chunked(
                mm.succ, segs, n_states=mm.n_states,
                n_transitions=mm.n_transitions, P=P_k,
                progress=progress,
                progress_interval_s=progress_interval_s,
                s_real=s_real)
    if r is not None:
        status, fail_seg, n_final = r
        info["engine"] = "pallas-fused"
        info["frontier_capacity"] = PSEG.F_TOP
        if status != LJ.UNKNOWN:
            info["time_s"] = _obs.monotonic() - t0
            return _device_verdict(mm, packed, segs, status, fail_seg,
                                   n_final, info)
        # kernel overflow: record the attempt, then escalate — the
        # final artifact must say WHICH engine produced the verdict
        # and what was tried on the way (a wide-P UNKNOWN used to be
        # indistinguishable from a capacity abort in filetest output)
        _note_tried(info, "pallas-fused", PSEG.F_TOP)

    # MXU frontier engine: P past the fused kernel's tiers but with
    # bounded in-flight (remap_slots makes P the max CONCURRENT open
    # calls) rides BFS-as-matmul expansion with the exact packed-key
    # dedup — its capacity ladder tops out at 2x the XLA ladder's, so
    # wide-P workloads that overflowed 65536 now get verdicts
    # (docs/architecture.md "The engine ladder").
    from . import mxu as MXU

    if MXU.serves(mm.n_states, mm.n_transitions, P2):
        return _analyze_mxu(mm, packed, segs, succ, P2, t0, info,
                            capacities=capacities,
                            progress=progress,
                            progress_interval_s=progress_interval_s,
                            s_real=s_real)

    # the adaptive engine's small tier: most segments' closed frontiers
    # are tiny (p50 ~ 8 configs on the register bench), so each segment
    # first runs at Fs and escalates to F per-segment on overflow (the
    # engine degrades to big-only when F is too small for the tier)
    info["engine"] = "xla-seg2"
    Fs = 32
    # Large histories ALWAYS run chunked, progress callback or not:
    # XLA compile time scales with the scan length, and a monolithic
    # 65536-segment program takes >10 min to compile per ladder level
    # where the 2048-chunk program compiles in ~35 s and streams the
    # remaining chunks in seconds (measured on a 117k-event P=18
    # history). Small histories keep the single-dispatch form — per-
    # chunk dispatch overhead would dominate them.
    chunked = (progress is not None
               or segs.ok_proc.shape[0] > CHUNKED_S_THRESHOLD)
    if not chunked:
        for F in capacities:
            with _obs.span("linear.escalate", engine="xla-seg2",
                           capacity=F):
                status, fail_seg, n_final = LJ.check_device_seg2(
                    succ, segs.inv_proc, segs.inv_tr, segs.ok_proc,
                    segs.depth, F=F, Fs=Fs, P=P2, **sizes)
                status = int(status)
            info["frontier_capacity"] = F
            if status != LJ.UNKNOWN:
                break
    else:
        # chunked, with IN-PLACE capacity escalation: an overflow
        # re-runs only the overflowing chunk with the boundary carry
        # widened to the next ladder level — a restart would repay
        # every already-checked chunk per level (on a 117k-event
        # history each full pass is ~40 s even warm)
        S = segs.ok_proc.shape[0]
        chunk = max(_next_pow2(min(S, 2048)), 64)
        cap_ix = 0
        F = capacities[cap_ix]
        carry = LJ.init_seg_carry(F, P2)
        t_run = _obs.monotonic()
        last = t_run
        done = 0
        visited = 0
        with _obs.span("linear.escalate", engine="xla-seg2") as sp:
            while done < S:
                end = min(done + chunk, S)
                pad = chunk - (end - done)
                ip = np.pad(segs.inv_proc[done:end],
                            ((0, pad), (0, 0)), constant_values=-1)
                it = np.pad(segs.inv_tr[done:end], ((0, pad), (0, 0)))
                op_ = np.pad(segs.ok_proc[done:end], (0, pad),
                             constant_values=-1)
                dp = np.pad(segs.depth[done:end], (0, pad))
                new_carry = LJ.check_device_seg2_chunk(
                    succ, ip, it, op_, dp, done, carry, F=F, Fs=Fs,
                    P=P2, **sizes)
                st = int(new_carry[4])
                if st == LJ.UNKNOWN and cap_ix + 1 < len(capacities):
                    cap_ix += 1
                    F = capacities[cap_ix]
                    carry = LJ.expand_seg_carry(carry, F)
                    continue            # same chunk, wider frontier
                carry = new_carry
                visited += int(carry[3]) * (end - done)
                done = end
                if st != LJ.VALID:
                    break
                now = _obs.monotonic()
                if progress is not None and \
                        now - last >= progress_interval_s:
                    # pending counts from the carry: telemetry parity
                    # with the reference's visited/s + estimated-cost
                    # reporters (core.clj:442-460, config.clj:374-393).
                    # Bucketed on device so only P+1 ints are read
                    # back per tick, never the (F, P) frontier
                    hist = np.asarray(LJ.pending_histogram(
                        carry[1], carry[2], P=P2))
                    el = max(now - t_run, 1e-9)
                    progress(min(done, s_real), s_real, int(carry[3]),
                             {"visited_per_s": visited / el,
                              "segs_per_s": done / el,
                              "est_cost": LJ.estimated_cost_hist(hist)})
                    last = now
            sp.set(capacity=F)
        status, fail_seg, n_final = (int(carry[4]), carry[5],
                                     carry[3])
        info["frontier_capacity"] = F
    info["time_s"] = _obs.monotonic() - t0
    return _device_verdict(mm, packed, segs, status, fail_seg, n_final,
                           info)


def _note_tried(info: dict, engine: str, capacity) -> None:
    """Record an engine attempt that did NOT produce the verdict —
    the artifact's ``engines_tried`` trail makes a wide-P UNKNOWN
    distinguishable from a capacity abort (each entry names the
    engine and the frontier capacity it gave up at)."""
    info.setdefault("engines_tried", []).append(
        {"engine": engine, "frontier_capacity": capacity})


def _analyze_mxu(mm: MemoizedModel, packed: PackedHistory, segs, succ,
                 P: int, t0: float, info: dict,
                 capacities: Sequence[int] = None, progress=None,
                 progress_interval_s: float = 5.0,
                 s_real: int = None) -> Analysis:
    """The MXU frontier engine's driver arm: capacity ladder over
    ``mxu.CAPACITIES`` with the same chunked / in-place-escalation
    discipline as the XLA arm (an overflow widens the PRE-chunk carry
    and re-runs only the overflowing chunk). Terminal for the shapes
    it serves: its top rung is 2x the XLA ladder's, so there is no
    wider engine to fall through to — overflow past it is the honest
    UNKNOWN, attributed to this engine in the artifact.

    ``capacities`` is the caller's ``analysis(capacities=...)`` bound:
    each entry buckets UP to the smallest ``mxu.CAPACITIES`` rung that
    holds it (the program surface stays closed on the declared rungs)
    and the ladder runs only those rungs — a caller bounding device
    work can force an early UNKNOWN here exactly like on the XLA arm.
    """
    import numpy as np

    from . import linear_jax as LJ
    from . import mxu as MXU

    if capacities is None:
        ladder = tuple(MXU.CAPACITIES)
    else:
        ladder = tuple(sorted({MXU.bucket_F(f) for f in capacities}))

    info["engine"] = "mxu-frontier"
    sizes = {"n_states": mm.n_states, "n_transitions": mm.n_transitions}
    S = segs.ok_proc.shape[0]
    if s_real is None:
        s_real = S
    chunked = (progress is not None or S > CHUNKED_S_THRESHOLD)
    if not chunked:
        for F in ladder:
            with _obs.span("linear.escalate", engine="mxu-frontier",
                           capacity=F):
                status, fail_seg, n_final = MXU.check_device_mxu(
                    succ, segs.inv_proc, segs.inv_tr, segs.ok_proc,
                    segs.depth, F=F, P=P, **sizes)
                status = int(status)
            info["frontier_capacity"] = F
            if status != LJ.UNKNOWN:
                break
    else:
        chunk = max(_next_pow2(min(S, MXU.CHUNK)), 64)
        cap_ix = 0
        F = ladder[cap_ix]
        carry = MXU.init_carry(1, F, P, **sizes)
        t_run = _obs.monotonic()
        last = t_run
        done = 0
        visited = 0
        with _obs.span("linear.escalate",
                       engine="mxu-frontier") as sp:
            while done < S:
                end = min(done + chunk, S)
                pad = chunk - (end - done)
                ip = np.pad(segs.inv_proc[done:end],
                            ((0, pad), (0, 0)), constant_values=-1)
                it = np.pad(segs.inv_tr[done:end], ((0, pad), (0, 0)))
                op_ = np.pad(segs.ok_proc[done:end], (0, pad),
                             constant_values=-1)
                dp = np.pad(segs.depth[done:end], (0, pad))
                new_carry = MXU.check_device_mxu_chunk(
                    succ, ip, it, op_, dp, done, carry, F=F, P=P, **sizes)
                st = int(new_carry[3][0])
                if st == LJ.UNKNOWN and cap_ix + 1 < len(ladder):
                    cap_ix += 1
                    F = ladder[cap_ix]
                    carry = MXU.expand_carry(carry, F)
                    continue            # same chunk, wider frontier
                carry = new_carry
                visited += int(carry[2][0]) * (end - done)
                done = end
                if st != LJ.VALID:
                    break
                now = _obs.monotonic()
                if progress is not None and \
                        now - last >= progress_interval_s:
                    hist = np.asarray(MXU.pending_histogram(
                        carry[0], carry[1], P=P, **sizes))
                    el = max(now - t_run, 1e-9)
                    # report against the REAL segment count like the XLA
                    # arm — S here is the pow2-padded axis
                    progress(min(done, s_real), s_real, int(carry[2][0]),
                             {"visited_per_s": visited / el,
                              "segs_per_s": done / el,
                              "est_cost": LJ.estimated_cost_hist(hist)})
                    last = now
            sp.set(capacity=F)
        status, fail_seg, n_final = (int(carry[3][0]), carry[4][0],
                                     carry[2][0])
        info["frontier_capacity"] = F
    info["time_s"] = _obs.monotonic() - t0
    return _device_verdict(mm, packed, segs, status, fail_seg, n_final,
                           info)


def _device_verdict(mm, packed, segs, status, fail_seg, n_final,
                    info) -> Analysis:
    """Decode an engine's (status, fail_segment, n) into an Analysis."""
    from . import linear_jax as LJ

    fail_at = (int(segs.seg_index[int(fail_seg)])
               if int(fail_seg) >= 0 else -1)
    if status == LJ.VALID:
        return Analysis(valid=True, final_count=int(n_final), info=info)
    if status == LJ.UNKNOWN:
        # attribute the give-up: which engine, at what capacity (plus
        # the engines_tried trail) — a wide-P overflow and a kernel
        # capacity abort used to render identically in the artifact
        cause = (f"frontier overflow (engine="
                 f"{info.get('engine', '?')}, capacity="
                 f"{info.get('frontier_capacity', '?')})")
        return Analysis(valid=UNKNOWN, op_index=fail_at,
                        info={**info, "cause": cause})
    # invalid: bounded counterexample reconstruction (the final-paths
    # role, linear.clj:180-212) — device re-scan to the failing chunk,
    # host replay of at most one chunk from the boundary carry, then
    # concrete failed linearization orders. Never re-runs the whole
    # history on host (round-1 Weak #3).
    op_index = fail_at
    op = packed.ops[op_index]
    cfgs: List[dict] = []
    try:
        from . import counterexample as CE
        # F >= the verdict's capacity: a larger frontier can't change
        # an INVALID verdict (overflow would have been UNKNOWN), and
        # the 256 floor shares compiles with the capacity ladder. The
        # re-scan runs the XLA chunk engine, whose ladder tops out at
        # 65536 — an MXU verdict from the 131072 rung clamps down
        # rather than compiling a frontier width the XLA engine never
        # otherwise sees (compile time scales with F; a re-scan
        # overflow at the clamp degrades to an undecorated INVALID)
        with _obs.span("linear.counterexample"):
            ce = CE.reconstruct(mm, packed,
                                F=max(256, min(info.get(
                                    "frontier_capacity", 256), 65536)))
        if ce is not None:
            cfgs = ce.configs
            op_index = ce.op_index
            op = packed.ops[op_index]
            info = {**info, "paths": ce.paths}
    except Exception as e:
        # decoration must never destroy an already-decided verdict: a
        # reconstruction failure (frontier overflow, compile error, …)
        # degrades to an un-annotated INVALID
        import logging

        logging.getLogger(__name__).warning(
            "counterexample reconstruction failed (%s: %s) — "
            "returning undecorated INVALID", type(e).__name__, e)
    return Analysis(valid=False, op=op, op_index=op_index, configs=cfgs,
                    info=info)
