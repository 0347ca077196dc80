"""TPU-native linearizability search — batched frontier expansion.

The device form of :mod:`comdb2_tpu.checker.linear_host` (which itself
carries the semantics of the reference's ``knossos/linear.clj``). Design:

- The config set becomes a *fixed-capacity frontier*: ``states:int32[F]``,
  ``slots:int32[F,P]``, ``valid:bool[F]``. ``slots`` is the tensor form of
  the reference's packed ``ArrayProcesses`` int arrays
  (``knossos/linear/config.clj:157-295``).
- The history becomes three device arrays (``kind/proc/tr``) consumed by
  one ``lax.scan``; each step switches on op kind. No Python control flow
  depends on data — the 50k-op scan is a single XLA computation.
- An ``ok`` op runs the linearization *closure* as a bounded
  ``lax.while_loop``: one iteration linearizes any single pending call in
  every config at once — an ``[F,P]`` gather into the memoized successor
  table (``succ``) — then dedups frontier ∪ candidates by sorting rows
  into an exact lexicographic order and compacting survivors to the
  front. This replaces the reference's per-op DFS + hash-set dedup
  (``linear.clj:66-129``, ``SetConfigSet``) with sort/segment primitives
  XLA maps well onto TPU.
- Frontier overflow ⇒ verdict ``:unknown`` — the semantics of the
  reference's low-memory abort (``linear.clj:318-326``). The driver
  (:mod:`.linear`) escalates capacity and retries, so small histories pay
  small sorts (the analog of the reference's 128-config pmap threshold,
  ``linear.clj:214-216``).

Dedup is exact: rows sort by their full contents, so every duplicate is
adjacent to its twin and merged (hash-fingerprint ordering is *not*
sound here — colliding non-identical rows can interleave between equal
rows and break adjacency, ballooning the frontier into spurious
overflow). The closure loop is additionally capped at P iterations
(closure depth is bounded by the number of pending calls), so
termination never depends on the heuristic change detector.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

IDLE = -1
LIN = -2

# op kinds in the precompiled step stream
K_SKIP = 0     # fail/info completions, failing invokes, padding
K_INVOKE = 1
K_OK = 2

# result status codes
VALID = 0
INVALID = 1
UNKNOWN = 2    # frontier overflow


class StepStream(NamedTuple):
    """Host-precompiled per-op step metadata (see :func:`make_stream`).
    Kept as host numpy arrays — jit transfers them once at check time;
    eagerly device_putting here costs a host-device transfer per array
    (and another one back for batch packing)."""
    kind: np.ndarray   # int32[n]
    proc: np.ndarray   # int32[n]
    tr: np.ndarray     # int32[n]


def make_stream(packed, n_pad: Optional[int] = None) -> StepStream:
    """Compile a PackedHistory into the device step stream. ``n_pad``
    pads with no-op steps so histories of similar length share one
    compiled program."""
    from ..ops.op import INVOKE, OK
    n = len(packed)
    n_pad = n_pad or n
    kind = np.zeros(n_pad, np.int32)
    proc = np.zeros(n_pad, np.int32)
    tr = np.zeros(n_pad, np.int32)
    for i in range(n):
        t = int(packed.type[i])
        if t == INVOKE and not packed.fails[i]:
            kind[i] = K_INVOKE
            proc[i] = packed.process[i]
            tr[i] = packed.trans[i]
        elif t == OK:
            kind[i] = K_OK
            proc[i] = packed.process[i]
    return StepStream(kind, proc, tr)


def estimated_cost(pending_counts) -> float:
    """Σ n·n! over configs — the reference's search-cost estimate by
    pending-call count (``knossos/linear/config.clj:374-393``): each
    config with n pending calls can spawn up to n·Γ(n+1) orders."""
    import math

    return float(sum(n * math.factorial(min(int(n), 12))
                     for n in pending_counts))


def estimated_cost_hist(hist) -> float:
    """:func:`estimated_cost` from a pending-count histogram
    (``hist[k]`` = configs with k pending calls)."""
    import math

    return float(sum(int(c) * k * math.factorial(min(k, 12))
                     for k, c in enumerate(hist)))


@functools.partial(jax.jit, static_argnames=("P",))
def pending_histogram(slots, valid, *, P: int):
    """Per-config pending-call counts bucketed on device: the progress
    telemetry needs only P+1 ints back to the host, not the
    whole (F, P) frontier."""
    pend = jnp.sum(slots >= 0, axis=1)
    return jnp.bincount(pend, weights=valid.astype(jnp.int32),
                        length=P + 1)


def pad_succ(succ: np.ndarray, s_pad: Optional[int] = None,
             t_pad: Optional[int] = None) -> np.ndarray:
    """Pad the successor table to bucketed shapes (recompile avoidance).
    Padding states/transitions are all-inconsistent (-1)."""
    S, T = succ.shape
    s_pad, t_pad = s_pad or S, t_pad or T
    out = np.full((s_pad, t_pad), -1, np.int32)
    out[:S, :T] = succ
    return out


def _greedy_split(widths):
    """Simulate the packers' greedy fill (lo from the field list's end,
    hi takes the rest); returns (lo_bits, hi_bits). Fields never
    straddle words, so the budget must be checked per word — summing
    total bits alone misses fragmentation and would let fields shift
    past bit 31, aliasing distinct configs."""
    lo_bits = 0
    i = len(widths) - 1
    while i >= 0 and lo_bits + widths[i] <= 31:
        lo_bits += widths[i]
        i -= 1
    return lo_bits, sum(widths[:i + 1])


def pack_bits(n_states: int, n_transitions: int, P: int):
    """Bit budget for packing one config (state + P slots) into two
    int32 words. Returns (state_bits, slot_bits, fits); fits is False
    when the greedy per-word split overflows (the engines then pack
    into MORE words — see :class:`PackPlan` — never a lossy key).
    Slot values live in [-2, T), stored as slot+2. hi must stay below
    bit 30: the invalid sentinel is 1<<30 and must sort after every
    valid key."""
    state_bits = max(int(np.ceil(np.log2(max(n_states, 2)))), 1)
    slot_bits = max(int(np.ceil(np.log2(max(n_transitions + 2, 2)))), 1)
    _, hi_bits = _greedy_split([state_bits] + [slot_bits] * P)
    fits = hi_bits <= 29 and state_bits <= 29 and slot_bits <= 29
    return state_bits, slot_bits, fits


class PackPlan(NamedTuple):
    """Exact lossless packing of one config (state + P slots) into
    ``n_words`` int32 sort keys — the wide-P generalization of the
    two-word budget (round-3 VERDICT #1: ``ArrayProcesses`` has no
    width limit, ``knossos/linear/config.clj:157-295``, and the
    reference CLI defaults to concurrency 30, ``cli.clj:52-91``).

    ``assign[i]`` is the (word, shift) of field i, fields =
    [state, slot_0, .., slot_{P-1}], filled greedily from the END of
    the list into word 0 (the least-significant sort key), then word
    1, ... Words hold <= 31 bits (values stay non-negative int32); the
    TOP word keeps bits 29/30 free for the okp-order flag and the
    invalid sentinel. Dedup sorts by all words (top = primary), so
    equal configs are adjacent — exact for ANY P, at W = ceil(bits/31)
    sort keys instead of the P+2 full-lexsort passes whose compile
    explodes at F >= 1024 (CLAUDE.md "STILL OPEN", now closed)."""
    state_bits: int
    slot_bits: int
    P: int
    assign: tuple          # ((word, shift), ...) per field
    n_words: int


def make_pack_plan(n_states: int, n_transitions: int,
                   P: int) -> Optional[PackPlan]:
    """Build the multi-word plan, or None when a single field exceeds
    29 bits (then only the full row lexsort is exact)."""
    state_bits = max(int(np.ceil(np.log2(max(n_states, 2)))), 1)
    slot_bits = max(int(np.ceil(np.log2(max(n_transitions + 2, 2)))), 1)
    widths = [state_bits] + [slot_bits] * P
    if max(widths) > 29:
        return None
    assign: list = [None] * len(widths)
    word, used = 0, 0
    for i in range(len(widths) - 1, -1, -1):
        if used + widths[i] > 31:
            word, used = word + 1, 0
        assign[i] = (word, used)
        used += widths[i]
    if used > 29:
        word += 1              # flags get a fresh top word
    return PackPlan(state_bits, slot_bits, P, tuple(assign), word + 1)


def _pack_plan_words(states, slots, plan: PackPlan):
    """Pack each config row into ``plan.n_words`` int32 words
    (word 0 least significant)."""
    fields = [states] + [slots[:, q] + 2 for q in range(plan.P)]
    words = [jnp.zeros_like(states) for _ in range(plan.n_words)]
    for f, (w, sh) in zip(fields, plan.assign):
        words[w] = words[w] | (f << sh)
    return words


def _dedup_compact(states, slots, valid, F, plan=None, okp=None):
    """Sort rows into an exact order (valid first) so identical configs
    are guaranteed adjacent; drop duplicates.
    Returns (states[F], slots[F,P], valid[F], n_unique, overflow).

    With a :class:`PackPlan`, rows pack losslessly into ``plan.n_words``
    int32 words — a W-key sort instead of P+2 stable sort passes;
    otherwise falls back to the full lexicographic sort. Both are exact:
    hash-fingerprint ordering is NOT sound here (colliding non-identical
    rows can interleave between equal rows and break adjacency).

    ``okp`` (a traced scalar process id) additionally orders rows whose
    slot ``okp`` is linearized (LIN) *before* all others. Equal rows
    share that predicate, so dedup adjacency is unaffected; the adaptive
    engine relies on it to keep the post-ok frontier a contiguous
    prefix (see :func:`check_device_seg2`)."""
    P = slots.shape[1]
    if okp is not None:
        not_ret = (jnp.take_along_axis(
            slots, jnp.full((slots.shape[0], 1), okp, jnp.int32),
            axis=1)[:, 0] != LIN).astype(jnp.int32)
    if plan is not None:
        words = _pack_plan_words(states, slots, plan)
        top = words[-1]
        if okp is not None:
            # the top word stays < 2^29 by the plan budget; bit 29 is
            # free and below the invalid sentinel (1 << 30)
            top = top | (not_ret << 29)
        top = jnp.where(valid, top, jnp.int32(1) << 30)  # invalid last
        words[-1] = top
        order = jnp.lexsort(tuple(words))
        ws = [w[order] for w in words]
        va = valid[order]
        pad = jnp.zeros(1, bool)
        eq = ws[0][1:] == ws[0][:-1]
        for w in ws[1:]:
            eq = eq & (w[1:] == w[:-1])
        same = jnp.concatenate([pad, eq & va[:-1]])
    else:
        # lexsort: last key is primary — valid rows first, full row order
        keys = tuple(slots[:, q] for q in range(P - 1, -1, -1)) \
            + (states,)
        if okp is not None:
            keys = keys + (not_ret,)
        keys = keys + (~valid,)
        order = jnp.lexsort(keys)
        st0, sl0, va = states[order], slots[order], valid[order]
        pad = jnp.zeros(1, bool)
        same = jnp.concatenate([pad, (st0[1:] == st0[:-1])
                                & jnp.all(sl0[1:] == sl0[:-1], axis=1)
                                & va[:-1]])
    keep = va & ~same
    n = jnp.sum(keep)
    # measured on v5e: a second small argsort beats cumsum+scatter
    # compaction here (~9.5k vs ~7.3k ops/s on the 50k bench); the
    # keys engine uses scatter because its row counts are larger
    # and block-structured
    order2 = jnp.argsort(~keep, stable=True)[:F]
    sel = order[order2]
    return states[sel], slots[sel], keep[order2], n, n > F


def _expand(succ, states, slots, valid):
    """One linearization step applied to every (config, pending call):
    returns F*P candidate rows (the vmapped ``t-lin``)."""
    F, P = slots.shape
    calling = slots >= 0
    s2 = succ[states[:, None], jnp.maximum(slots, 0)]          # [F,P]
    cand_valid = (valid[:, None] & calling & (s2 >= 0)).reshape(F * P)
    cand_slots = jnp.broadcast_to(slots[:, None, :], (F, P, P))
    cand_slots = cand_slots.at[:, jnp.arange(P), jnp.arange(P)].set(LIN)
    return s2.reshape(F * P), cand_slots.reshape(F * P, P), cand_valid


def _closure(succ, states, slots, valid, n_valid, F, P, plan,
             max_iter=None, okp=None):
    """Fixed point of single-call linearization with dedup.
    ``max_iter`` bounds iterations exactly (= pending-call count, the
    longest possible linearization chain); defaults to the loose P+1
    bound. ``okp`` orders returning rows first in every dedup (see
    :func:`_dedup_compact`)."""
    if max_iter is None:
        max_iter = P + 1

    def cond(c):
        _, _, _, _, changed, overflow, it = c
        return changed & ~overflow & (it < max_iter)

    def body(c):
        st, sl, va, n, _, _, it = c
        c_st, c_sl, c_va = _expand(succ, st, sl, va)
        all_st = jnp.concatenate([st, c_st])
        all_sl = jnp.concatenate([sl, c_sl])
        all_va = jnp.concatenate([va, c_va])
        st2, sl2, va2, n2, ovf = _dedup_compact(all_st, all_sl, all_va,
                                                F, plan=plan, okp=okp)
        return st2, sl2, va2, n2, n2 > n, ovf, it + 1

    init = body((states, slots, valid, n_valid,
                 jnp.bool_(True), jnp.bool_(False), jnp.int32(0)))
    st, sl, va, n, _, ovf, _ = lax.while_loop(cond, body, init)
    return st, sl, va, n, ovf


def _make_step(succ, F, P, bits):
    def step(carry, op):
        states, slots, valid, n, status, fail_at = carry
        kind, proc, tr, idx = op

        def do_invoke(_):
            return (states, slots.at[:, proc].set(tr), valid, n,
                    status, fail_at)

        def do_ok(_):
            st, sl, va, _, ovf = _closure(succ, states, slots, valid, n,
                                          F, P, bits)
            returned = va & (sl[:, proc] == LIN)
            sl2 = sl.at[:, proc].set(IDLE)
            n2 = jnp.sum(returned)
            st_new = jnp.where(ovf, UNKNOWN,
                               jnp.where(n2 == 0, INVALID, VALID))
            return (st, sl2, returned, n2, st_new.astype(jnp.int32),
                    jnp.where(st_new == VALID, fail_at, idx))

        def dispatch(_):
            return lax.switch(kind, [lambda _: carry, do_invoke, do_ok], None)

        carry2 = lax.cond(status == VALID, dispatch, lambda _: carry, None)
        return carry2, None

    return step


def _check_impl(succ, kind, proc, tr, F: int, P: int,
                bits=None):
    n_ops = kind.shape[0]
    states = jnp.zeros(F, jnp.int32)
    slots = jnp.full((F, P), IDLE, jnp.int32)
    valid = jnp.zeros(F, bool).at[0].set(True)
    carry = (states, slots, valid, jnp.int32(1), jnp.int32(VALID),
             jnp.int32(-1))
    ops = (kind, proc, tr, jnp.arange(n_ops, dtype=jnp.int32))
    step = _make_step(succ, F, P, bits)
    (states, slots, valid, n, status, fail_at), _ = lax.scan(
        step, carry, ops)
    return status, fail_at, n


def _bits_for(n_states, n_transitions, P):
    """Static :class:`PackPlan` for the multi-word packed dedup, or
    None (→ full row lexsort) when the true memo sizes are unknown or
    a single field won't fit a word."""
    if n_states is None or n_transitions is None:
        return None
    return make_pack_plan(n_states, n_transitions, P)


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device(succ, kind, proc, tr, *, F: int, P: int,
                 n_states=None, n_transitions=None):
    """Run the full search for one history on device.

    Returns ``(status, fail_index, n_final)`` — status is VALID/INVALID/
    UNKNOWN; fail_index is the history index of the op at which the
    frontier died (or overflowed). Passing the true (unpadded)
    ``n_states``/``n_transitions`` enables the multi-word packed dedup
    (see :class:`PackPlan`; any P whose fields fit 29 bits)."""
    bits = _bits_for(n_states, n_transitions, P)
    return _check_impl(succ, kind, proc, tr, F, P, bits)


# --- segmented stream: one device step per ok-op ---------------------------

class SegmentStream(NamedTuple):
    """Host-precompiled segments (see :func:`make_segments`): segment i
    carries the invokes since the previous ok (padded to K) plus the
    ok's process. ``seg_index`` maps segment → history index of its ok
    (host-side, for decoding fail_at). ``depth`` is the number of
    pending calls at the ok — the exact closure-iteration bound (a
    linearization chain can't be longer than the pending set)."""
    inv_proc: np.ndarray   # int32[S, K], -1 padding
    inv_tr: np.ndarray     # int32[S, K]
    ok_proc: np.ndarray    # int32[S]
    seg_index: np.ndarray  # int64[S] (host side only)
    depth: np.ndarray      # int32[S]


def make_segments(packed, s_pad: Optional[int] = None,
                  k_pad: Optional[int] = None) -> SegmentStream:
    """Compress a history into per-ok segments.

    The per-row scan spends a sequential step on every history row;
    but only ok-ops change the frontier's validity — invokes just set a
    slot, and fail/info rows are no-ops (``linear.clj:226``). Folding
    each run of invokes into its following ok yields one device step
    per ok-op (~3x fewer sequential steps). Invokes after the final ok
    are dropped: a pending call can only *add* linearization orders,
    never empty a non-empty frontier.

    Columnar since the host-ingest rebuild (one stable argsort for the
    per-process pending-discipline check, cumsums for depths, one
    scatter for the (S, K) fill — bit-identical to the per-op walk,
    which survives one release behind ``COMDB2_TPU_LEGACY_PACK=1``)."""
    from ..ops.packed import legacy_pack_enabled

    if legacy_pack_enabled():
        return make_segments_legacy(packed, s_pad=s_pad, k_pad=k_pad)
    from ..ops.op import INVOKE, OK, FAIL

    from ..ops.columnar import _per_process_prev

    t = np.asarray(packed.type)
    proc = np.asarray(packed.process)
    tra = np.asarray(packed.trans)
    fl = np.asarray(packed.fails)
    n = t.shape[0]
    vinv = (t == INVOKE) & ~fl
    okm = t == OK
    failm = t == FAIL
    removal = np.zeros(n, bool)
    sel = np.flatnonzero(vinv | okm | failm)
    if sel.size:
        # per-process event chains: pending_p is {0,1} (add on a
        # non-failing invoke, clear on ok/fail), so "p was pending" ==
        # "p's previous selected event was a non-failing invoke"
        srt, vflag, prev_v, _ = _per_process_prev(proc, sel, vinv)
        dbl = vflag & prev_v
        if dbl.any():
            i = int(srt[dbl].min())
            raise ValueError(
                f"process {int(proc[i])} invokes at row {i} while an "
                "earlier invocation is still pending — malformed "
                "history")
        removal[srt[~vflag & prev_v]] = True
    ok_idx = np.flatnonzero(okm)
    S = ok_idx.size
    cum_rem = np.cumsum(removal)
    depth_vals = (np.cumsum(vinv)[ok_idx]
                  - (cum_rem[ok_idx] - removal[ok_idx]))
    cum_ok_excl = np.cumsum(okm) - okm
    inv_rows = np.flatnonzero(vinv)
    seg_of = cum_ok_excl[inv_rows]
    keep = seg_of < S              # invokes after the final ok drop
    inv_rows, seg_of = inv_rows[keep], seg_of[keep]
    if inv_rows.size:
        kpos = (np.arange(inv_rows.size)
                - np.searchsorted(seg_of, seg_of, side="left"))
        K = int(np.bincount(seg_of).max()) or 1
    else:
        kpos = seg_of
        K = 1
    k_pad = max(k_pad or 0, K)
    s_pad = max(s_pad or 0, S)
    inv_proc = np.full((s_pad, k_pad), -1, np.int32)
    inv_tr = np.zeros((s_pad, k_pad), np.int32)
    inv_proc[seg_of, kpos] = proc[inv_rows]
    inv_tr[seg_of, kpos] = tra[inv_rows]
    ok_proc = np.full(s_pad, -1, np.int32)   # -1 = padding segment
    seg_index = np.zeros(s_pad, np.int64)
    depth = np.zeros(s_pad, np.int32)
    ok_proc[:S] = proc[ok_idx]
    seg_index[:S] = ok_idx
    depth[:S] = depth_vals
    return SegmentStream(inv_proc, inv_tr, ok_proc, seg_index, depth)


def pad_segments(segs: SegmentStream, s_pad: int,
                 k_pad: int) -> SegmentStream:
    """Pad a built stream to ``(s_pad, k_pad)`` with the fill values of
    :func:`make_segments`' own pads (-1 for ``inv_proc``/``ok_proc``,
    0 elsewhere), so ``pad_segments(make_segments(p), s, k)`` equals
    ``make_segments(p, s, k)`` without building twice. Pads are floors,
    as there."""
    S, K = segs.inv_proc.shape
    ds, dk = max(s_pad - S, 0), max(k_pad - K, 0)
    if not (ds or dk):
        return segs
    return SegmentStream(
        np.pad(segs.inv_proc, ((0, ds), (0, dk)), constant_values=-1),
        np.pad(segs.inv_tr, ((0, ds), (0, dk))),
        np.pad(segs.ok_proc, (0, ds), constant_values=-1),
        np.pad(segs.seg_index, (0, ds)),
        np.pad(segs.depth, (0, ds)))


def make_segments_legacy(packed, s_pad: Optional[int] = None,
                         k_pad: Optional[int] = None) -> SegmentStream:
    """The original per-op segment walk (see :func:`make_segments`)."""
    from ..ops.op import INVOKE, OK, FAIL
    n = len(packed)
    segs: list = []
    cur: list = []
    pending: set = set()
    # plain lists: per-element numpy scalar indexing is ~10x slower
    # and this loop runs over every row of every history in a batch
    types = packed.type.tolist()
    procs = packed.process.tolist()
    transs = packed.trans.tolist()
    failss = packed.fails.tolist()
    for i in range(n):
        t = types[i]
        p = procs[i]
        if t == INVOKE and not failss[i]:
            if p in pending:
                # the fused kernel applies invokes as relative deltas on
                # an IDLE slot and the XLA engines as absolute sets — a
                # double-pending process would silently diverge between
                # them, so reject it here (history.complete already
                # raises on the public path; this guards direct callers)
                raise ValueError(
                    f"process {p} invokes at row {i} while an earlier "
                    "invocation is still pending — malformed history")
            cur.append((p, transs[i]))
            pending.add(p)
        elif t == OK:
            segs.append((cur, p, i, len(pending)))
            pending.discard(p)
            cur = []
        elif t == FAIL:
            pending.discard(p)
    S = len(segs)
    K = max((len(c) for c, _, _, _ in segs), default=1) or 1
    # pads are FLOORS: callers bucketing many histories into one fixed
    # shape pass the bucket's (S, K); the actual maxima still win so
    # padding can never truncate a real segment
    k_pad = max(k_pad or 0, K)
    s_pad = max(s_pad or 0, S)
    inv_proc = np.full((s_pad, k_pad), -1, np.int32)
    inv_tr = np.zeros((s_pad, k_pad), np.int32)
    ok_proc = np.full(s_pad, -1, np.int32)   # -1 = padding segment
    seg_index = np.zeros(s_pad, np.int64)
    depth = np.zeros(s_pad, np.int32)
    for s, (calls, okp, idx, dep) in enumerate(segs):
        for k, (p, tr) in enumerate(calls):
            inv_proc[s, k] = p
            inv_tr[s, k] = tr
        ok_proc[s] = okp
        seg_index[s] = idx
        depth[s] = dep
    return SegmentStream(inv_proc, inv_tr, ok_proc, seg_index, depth)


def remap_slots(segs: SegmentStream, with_maps: bool = False):
    """Rename process ids in a segment stream to a minimal pool of
    reusable SLOTS. A process occupies a slot only while its call is
    open (invoke .. ok); the assignment is determined by the history
    alone — identical for every config — so renaming is a pure
    relabeling: verdicts, fail segments, and frontier sizes are
    unchanged. The effective slot count becomes the maximum number of
    CONCURRENT open calls, not the process count, which is what gates
    the fused kernel's tiers (``pallas_seg.spec_for``): a concurrency-10
    register history with <=6 calls in flight runs the (8,128)/2-word
    tier instead of the ~45%-slower (16,128)/3-word one, and histories
    with hundreds of processes but bounded concurrency become
    kernel-eligible at all. The reference's ``ArrayProcesses`` packs
    per-process cells the same dense way but never reuses them
    (``knossos/linear/config.clj:157-295``); reuse is safe here because
    an ok'd slot is IDLE in every surviving config before the stream
    can reassign it.

    Allocation is lowest-free-first within each segment's invoke list,
    releases happen after the segment's ok — so a slot freed by segment
    s is reusable from segment s+1 on. :info invokes never complete and
    hold their slot for the rest of the stream (process retirement —
    the retired id never invokes again, ``core.clj:178-200``). One
    Python pass visits the real cells only, never the padding; the free
    slots are an int bitmask (Python ints are unbounded, so any P).

    Returns ``(segs', P_eff)``, plus ``proc_of_slot`` (int32[S, P_eff];
    row s = which ORIGINAL process owns each slot after segment s, -1
    when free) when ``with_maps`` — the inverse needed to decode a
    device frontier back into process-indexed configs
    (:func:`comdb2_tpu.checker.counterexample.reconstruct`).
    """
    S, K = segs.inv_proc.shape
    # every real cell in allocation order — segment by segment, its
    # invokes in k order, then its ok — is row-major order over the
    # invokes with each ok appended as column K
    ext = np.empty((S, K + 1), np.int64)
    ext[:, :K] = segs.inv_proc
    ext[:, K] = ~segs.ok_proc.astype(np.int64)     # oks as ~p < 0
    real = np.empty((S, K + 1), bool)
    real[:, :K] = segs.inv_proc >= 0
    real[:, K] = segs.ok_proc >= 0
    at = np.flatnonzero(real)
    seg = at // (K + 1)
    slot_of: dict = {}
    free = 0          # bitmask of free slots; lowest-free is free & -free
    n_slots = 0
    out: list = []
    for p in ext.reshape(-1)[at].tolist():
        if p >= 0:
            if p in slot_of:
                raise ValueError(
                    f"process {p} invokes in segment {int(seg[len(out)])} "
                    "while an earlier invocation is still open")
            if free:
                low = free & -free
                free ^= low
                sl = low.bit_length() - 1
            else:
                sl = n_slots
                n_slots += 1
            slot_of[p] = sl
        else:
            sl = slot_of.pop(~p, None)
            if sl is None:
                # ok without an open invocation: the process's slot is
                # IDLE in every config, so the ok filter empties the
                # frontier (INVALID at this segment). Any free slot is
                # IDLE everywhere too — map to one to preserve exactly
                # that instead of rejecting the stream.
                if free:
                    sl = (free & -free).bit_length() - 1
                else:
                    sl = n_slots
                    n_slots += 1
                    free |= 1 << sl
            else:
                free |= 1 << sl
        out.append(sl)
    slots = np.asarray(out, np.int32)
    is_ok = at % (K + 1) == K
    inv = ~is_ok
    out_ip = np.array(segs.inv_proc, np.int32, order="C")
    out_ip.reshape(-1)[at[inv] - seg[inv]] = slots[inv]
    out_ok = np.array(segs.ok_proc, np.int32)
    out_ok[seg[is_ok]] = slots[is_ok]
    P_eff = n_slots
    segs2 = SegmentStream(out_ip, segs.inv_tr, out_ok,
                          segs.seg_index, segs.depth)
    if not with_maps:
        return segs2, P_eff
    # owner of each slot after each segment: an invoke sets its owner
    # at its segment, an ok clears it (written second, so an ok after an
    # invoke in the same segment wins; an unmatched ok's slot is free,
    # so clearing it changes nothing), then each slot's column is
    # forward-filled from its last change
    W = max(P_eff, 1)
    changed = np.zeros((S, W), bool)
    owner = np.full((S, W), -1, np.int32)
    changed[seg, slots] = True
    owner[seg[inv], slots[inv]] = ext.reshape(-1)[at[inv]]
    owner[seg[is_ok], slots[is_ok]] = -1
    last = np.where(changed, np.arange(S)[:, None], -1)
    np.maximum.accumulate(last, axis=0, out=last)
    pos = np.where(last >= 0, owner[np.maximum(last, 0), np.arange(W)], -1)
    return segs2, P_eff, pos


def remap_slots_batch(streams):
    """Batched :func:`remap_slots` over many SegmentStreams at once —
    the batch ingest path's form (``checker.batch._stream_segments``).
    Returns ``(streams', p_effs)`` with outputs BIT-IDENTICAL to
    per-history ``remap_slots`` (golden parity tests).

    The per-history pass is inherently sequential (lowest-free-first
    allocation with out-of-order release), but every history advances
    its segment clock independently — so the loop runs over SEGMENT
    POSITIONS with all histories as one vector lane each: state is a
    (B, n_procs) slot map plus a (B, P) in-use mask, and each step is
    a handful of numpy ops instead of B iterations of Python. The
    lowest-free rule maps onto ``argmax(~used)`` exactly: slots are
    allocated contiguously, so the smallest unused index is min(free
    heap) when the heap is non-empty and the fresh index otherwise."""
    B = len(streams)
    if B == 0:
        return [], []
    S_max = max(s.ok_proc.shape[0] for s in streams)
    K_max = max(s.inv_proc.shape[1] for s in streams)
    if S_max == 0 or all(int(s.ok_proc.shape[0]) == 0 for s in streams):
        return list(streams), [0] * B
    ip = np.full((B, S_max, K_max), -1, np.int32)
    okp = np.full((B, S_max), -1, np.int32)
    for b, s in enumerate(streams):
        sb, kb = s.inv_proc.shape
        ip[b, :sb, :kb] = s.inv_proc
        okp[b, :sb] = s.ok_proc
    npc = int(max(ip.max(initial=-1), okp.max(initial=-1), 0)) + 1
    slot_of = np.full((B, max(npc, 1)), -1, np.int32)
    # conservative live-slot bound (every ok treated as a release);
    # unmatched-ok edge allocations can exceed it — grown on demand
    opens = np.cumsum((ip >= 0).sum(axis=2), axis=1)
    rel = np.cumsum(okp >= 0, axis=1)
    p_cap = int(max((opens[:, 1:] - rel[:, :-1]).max(initial=0),
                    opens[:, 0].max(initial=0), 1)) + 1
    used = np.zeros((B, p_cap), bool)
    n_slots = np.zeros(B, np.int32)
    out_ip = ip.copy()
    out_ok = okp.copy()
    bidx = np.arange(B)
    for s in range(S_max):
        for k in range(K_max):
            p = ip[:, s, k]
            m = p >= 0
            if not m.any():
                continue
            pc = np.where(m, p, 0)
            if np.any(m & (slot_of[bidx, pc] >= 0)):
                b = int(np.flatnonzero(m & (slot_of[bidx, pc] >= 0))[0])
                raise ValueError(
                    f"process {int(p[b])} invokes in segment {s} while "
                    "an earlier invocation is still open")
            while np.any(m & used.all(axis=1)):
                used = np.pad(used, ((0, 0), (0, used.shape[1])))
            sl = np.argmax(~used, axis=1).astype(np.int32)
            out_ip[m, s, k] = sl[m]
            used[bidx[m], sl[m]] = True
            slot_of[bidx[m], pc[m]] = sl[m]
            n_slots = np.maximum(n_slots, np.where(m, sl + 1, 0))
        o = okp[:, s]
        m = o >= 0
        if not m.any():
            continue
        oc = np.where(m, o, 0)
        sl = slot_of[bidx, oc]
        matched = m & (sl >= 0)
        out_ok[matched, s] = sl[matched]
        used[bidx[matched], sl[matched]] = False
        slot_of[bidx[matched], oc[matched]] = -1
        un = m & ~matched
        if un.any():
            # ok with no open invocation: any free slot is IDLE in
            # every config — reference one (fresh if none), leaving it
            # free, exactly like the per-history path
            while np.any(un & used.all(axis=1)):
                used = np.pad(used, ((0, 0), (0, used.shape[1])))
            fs = np.argmax(~used, axis=1).astype(np.int32)
            out_ok[un, s] = fs[un]
            n_slots = np.maximum(n_slots, np.where(un, fs + 1, 0))
    out = []
    for b, s in enumerate(streams):
        sb, kb = s.inv_proc.shape
        out.append(SegmentStream(
            np.ascontiguousarray(out_ip[b, :sb, :kb]), s.inv_tr,
            np.ascontiguousarray(out_ok[b, :sb]),
            s.seg_index, s.depth))
    return out, [int(x) for x in n_slots]


def _make_seg_step(succ, F, P, K, bits, Fs=None):
    """One scan step over a segment. With ``Fs`` set (adaptive
    two-tier, see :func:`check_device_seg2`) the closure first runs at
    the small capacity and escalates to ``F`` per segment on overflow;
    without it the closure always runs at ``F``."""
    pad_f = F - Fs if Fs else 0

    def step(carry, seg):
        states, slots, valid, n, status, fail_at = carry
        inv_proc, inv_tr, ok_proc, sidx, depth = seg

        def run(_):
            sl = slots
            for k in range(K):      # unrolled: K is small and static
                p = inv_proc[k]
                sl = jnp.where(p >= 0,
                               sl.at[:, jnp.maximum(p, 0)]
                               .set(inv_tr[k]),
                               sl)

            def big(_):
                return _closure(succ, states, sl, valid, n, F, P, bits,
                                max_iter=depth, okp=ok_proc)

            if Fs is None:
                st, sl2, va, _, ovf = big(None)
            else:
                # the small tier runs unconditionally (its cost is what
                # the tiering saves; on segments it can't serve, the
                # result is discarded), then ONE cond selects the big
                # closure — so each closure body is compiled exactly
                # once. The big retry starts from the same pre-closure
                # frontier: whenever n <= Fs, rows Fs..F are invalid,
                # so `big` sees the identical config set.
                cst, csl, cva, cn, covf = _closure(
                    succ, states[:Fs], sl[:Fs], valid[:Fs], n, Fs,
                    P, bits, max_iter=depth, okp=ok_proc)

                def use_small(_):
                    return (jnp.concatenate(
                                [cst, jnp.zeros(pad_f, jnp.int32)]),
                            jnp.concatenate(
                                [csl, jnp.zeros((pad_f, P),
                                                jnp.int32)]),
                            jnp.concatenate(
                                [cva, jnp.zeros(pad_f, bool)]),
                            cn, jnp.bool_(False))

                need_big = (n > Fs) | covf
                st, sl2, va, _, ovf = lax.cond(need_big, big,
                                               use_small, None)

            returned = va & (sl2[:, ok_proc] == LIN)
            sl3 = sl2.at[:, ok_proc].set(IDLE)
            n2 = jnp.sum(returned)
            st_new = jnp.where(ovf, UNKNOWN,
                               jnp.where(n2 == 0, INVALID, VALID))
            return (st, sl3, returned, n2, st_new.astype(jnp.int32),
                    jnp.where(st_new == VALID, fail_at, sidx))

        live = (status == VALID) & (ok_proc >= 0)
        carry2 = lax.cond(live, run, lambda _: carry, None)
        return carry2, None

    return step


def _check_impl_seg(succ, inv_proc, inv_tr, ok_proc, depth, F: int,
                    P: int, bits=None):
    S, K = inv_proc.shape
    carry = init_seg_carry(F, P)
    segs = (inv_proc, inv_tr, ok_proc,
            jnp.arange(S, dtype=jnp.int32), depth)
    step = _make_seg_step(succ, F, P, K, bits)
    (states, slots, valid, n, status, fail_at), _ = lax.scan(
        step, carry, segs)
    return status, fail_at, n


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_seg(succ, inv_proc, inv_tr, ok_proc, depth, *, F: int,
                     P: int, n_states=None, n_transitions=None):
    """Segmented single-history search: one sequential device step per
    ok-op. ``fail_at`` is a *segment* index — map through
    ``SegmentStream.seg_index`` on host."""
    bits = _bits_for(n_states, n_transitions, P)
    return _check_impl_seg(succ, inv_proc, inv_tr, ok_proc, depth, F, P,
                           bits)


def init_seg_carry(F: int, P: int):
    """Initial scan carry for the chunked segmented search."""
    states = jnp.zeros(F, jnp.int32)
    slots = jnp.full((F, P), IDLE, jnp.int32)
    valid = jnp.zeros(F, bool).at[0].set(True)
    return (states, slots, valid, jnp.int32(1), jnp.int32(VALID),
            jnp.int32(-1))


def expand_seg_carry(carry, F_new: int):
    """Widen a GOOD chunk-boundary carry to a larger frontier capacity:
    in-place escalation resumes the search at the overflowing chunk
    instead of restarting the whole history at the next ladder level
    (each restart repays every chunk already checked). Status/fail are
    reset — the carry must come from before the overflow."""
    states, slots, valid, count, _status, _fail = carry
    pad = F_new - states.shape[0]
    if pad < 0:
        raise ValueError("carry wider than target capacity")
    states = jnp.pad(states, (0, pad))
    slots = jnp.pad(slots, ((0, pad), (0, 0)), constant_values=IDLE)
    valid = jnp.pad(valid, (0, pad))
    return (states, slots, valid, count, jnp.int32(VALID),
            jnp.int32(-1))


def expand_seg_carry_slots(carry, P_new: int):
    """Widen a carry's SLOT axis in place (streaming sessions whose
    effective concurrency grows mid-stream): new slots pad IDLE, which
    leaves every config's semantics unchanged — a relabeled key layout
    is still exact, and the renamed segment streams only ever address
    slots below the renamer's running P_eff. Status/fail/count are
    preserved: this is a mid-stream widening, not a capacity
    escalation.

    HOST numpy on purpose (like ``mxu.expand_carry``): widenings are
    rare, and an eager device pad here would compile an infra program
    outside the declared compile surface per carry shape — the next
    delta's jit transfers the widened carry instead."""
    states, slots, valid, count, status, fail = (np.asarray(x)
                                                 for x in carry)
    pad = P_new - slots.shape[1]
    if pad < 0:
        raise ValueError("carry has more slots than target width")
    if pad:
        slots = np.pad(slots, ((0, 0), (0, pad)),
                       constant_values=IDLE)
    return (states, slots, valid, count, status, fail)


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_seg_chunk(succ, inv_proc, inv_tr, ok_proc, depth,
                           seg_offset, carry, *, F: int, P: int,
                           n_states=None, n_transitions=None):
    """One chunk of the segmented search: consumes ``carry`` (from
    :func:`init_seg_carry` or a previous chunk) and returns the updated
    carry. Chunking lets the host report progress between device calls
    — the role of the reference's 5-second reporter threads
    (``knossos/linear.clj:273-297``). ``seg_offset`` biases the segment
    indices recorded in ``fail_at``."""
    bits = _bits_for(n_states, n_transitions, P)
    S = inv_proc.shape[0]
    segs = (inv_proc, inv_tr, ok_proc,
            seg_offset + jnp.arange(S, dtype=jnp.int32), depth)
    step = _make_seg_step(succ, F, P, inv_proc.shape[1], bits)
    carry2, _ = lax.scan(step, carry, segs)
    return carry2


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_seg_batch(succ, inv_proc, inv_tr, ok_proc, depth, *,
                           F: int, P: int, n_states=None,
                           n_transitions=None):
    """The batch path's last resort: the single-history segmented
    search vmapped over the lanes of the same (S, B, K) segment
    tensors the keys and MXU engines take. No packed-key budget, so it
    serves any table size and slot width; exact at ``F``, UNKNOWN on
    overflow. ``depth`` (S,) is the per-segment maximum over lanes, a
    bound on every lane's closure. Returns per-lane (status[B],
    fail_segment[B], n_final[B])."""
    bits = _bits_for(n_states, n_transitions, P)
    fn = functools.partial(_check_impl_seg, succ, F=F, P=P, bits=bits)
    return jax.vmap(fn, in_axes=(1, 1, 1, None))(
        inv_proc, inv_tr, ok_proc, depth)


# --- adaptive two-tier segmented engine ------------------------------------
#
# The dedup sort dominates a closure iteration, and its cost scales with
# the frontier capacity — but capacity is sized for the *worst* segment
# while typical segments need a fraction of it (measured on the 50k
# register bench: p50 closed-frontier = 8 configs, 96% <= 32, max 88).
# So each segment first runs the closure at a small capacity ``Fs`` and
# escalates to the full ``F`` only when Fs overflows — a per-segment
# lax.cond, the device analog of the reference's parallel-threshold
# laddering (linear.clj:214-216).
#
# Slicing the first Fs rows is sound because the engine maintains the
# invariant that valid configs form a contiguous prefix: every dedup
# compacts valid rows to the front, and ordering rows whose ok-slot is
# linearized first (okp in _dedup_compact) makes the post-ok surviving
# set a prefix too.

def _seg2_tier(Fs, F):
    """Small-tier capacity actually used: None (big-only) when the
    requested tier can't sit strictly below F."""
    return Fs if (Fs is not None and 0 < Fs < F) else None


@functools.partial(jax.jit, static_argnames=("F", "Fs", "P", "n_states",
                                             "n_transitions"))
def check_device_seg2(succ, inv_proc, inv_tr, ok_proc, depth, *, F: int,
                      P: int, Fs: int = 32, n_states=None,
                      n_transitions=None):
    """Adaptive segmented search: small-capacity closure with
    per-segment escalation to ``F``. Same inputs/outputs as
    :func:`check_device_seg`. A ``Fs`` that can't sit below ``F``
    degrades to the big-only engine instead of failing."""
    bits = _bits_for(n_states, n_transitions, P)
    S, K = inv_proc.shape
    carry = init_seg_carry(F, P)
    segs = (inv_proc, inv_tr, ok_proc, jnp.arange(S, dtype=jnp.int32),
            depth)
    step = _make_seg_step(succ, F, P, K, bits, Fs=_seg2_tier(Fs, F))
    (st, sl, va, n, status, fail_at), _ = lax.scan(step, carry, segs)
    return status, fail_at, n


@functools.partial(jax.jit, static_argnames=("F", "Fs", "P", "n_states",
                                             "n_transitions"))
def check_device_seg2_chunk(succ, inv_proc, inv_tr, ok_proc, depth,
                            seg_offset, carry, *, F: int, P: int,
                            Fs: int = 32, n_states=None,
                            n_transitions=None):
    """Chunked adaptive search (see :func:`check_device_seg_chunk`)."""
    bits = _bits_for(n_states, n_transitions, P)
    S = inv_proc.shape[0]
    segs = (inv_proc, inv_tr, ok_proc,
            seg_offset + jnp.arange(S, dtype=jnp.int32), depth)
    step = _make_seg_step(succ, F, P, inv_proc.shape[1], bits,
                          Fs=_seg2_tier(Fs, F))
    carry2, _ = lax.scan(step, carry, segs)
    return carry2


# --- key-packed batch engine: the frontier IS the sort key -----------------
#
# B histories in one flat frontier tensor with the batch id packed into
# the top bits of the sort key, so every step is plain big-array ops
# (vmapping the single-history search lowers ~20x worse per lane on
# TPU). Each config is ONLY its packed (hi, lo) int32 pair
# — state and slots are bit fields, never materialized as arrays.
# Invoking, linearizing, and returning ops are field arithmetic
# (deltas shifted into place); deduplication sorts the keys themselves.
# This removes the (rows, P, P) candidate materialization that
# dominates the explicit-tensor engines (measured ~3x the cost of the
# sort) and shrinks frontier memory from (P+1) words/row to 2.
#
# Field layout, LSB→MSB: slot_0 .. slot_{P-1}, state, invalid, batch —
# split across lo (bits 0..30) then hi. Slot values: 0 = linearized
# (LIN), 1 = idle (IDLE), t+2 = pending transition t. No field ever
# crosses the word boundary; field deltas never borrow into neighbors
# because every mutation keeps the field in range.

class KeyLayout:
    """Static (word, shift) assignment for each field."""

    def __init__(self, B: int, n_states: int, n_transitions: int,
                 P: int):
        self.P = P
        self.slot_bits = max(int(np.ceil(
            np.log2(max(n_transitions + 2, 2)))), 1)
        self.state_bits = max(int(np.ceil(
            np.log2(max(n_states, 2)))), 1)
        self.batch_bits = max(int(np.ceil(np.log2(max(B, 2)))), 1)
        fields = ([("slot", q, self.slot_bits) for q in range(P)]
                  + [("state", 0, self.state_bits),
                     ("invalid", 0, 1),
                     ("batch", 0, self.batch_bits)])
        self.pos = {}
        word, shift = 0, 0
        for name, idx, width in fields:
            if shift + width > 31:
                word, shift = word + 1, 0
            if width > 31 or word > 1:
                self.fits = False
                return
            self.pos[(name, idx)] = (word, shift)
            shift += width
        self.fits = True
        self.single_word = all(w == 0 for w, _ in self.pos.values())

    def get(self, hi, lo, name, idx=0):
        word, shift = self.pos[(name, idx)]
        width = {"slot": self.slot_bits, "state": self.state_bits,
                 "invalid": 1, "batch": self.batch_bits}[name]
        src = lo if word == 0 else hi
        return (src >> shift) & ((1 << width) - 1)

    def add(self, hi, lo, name, idx, delta):
        """Add a (possibly negative, data-dependent) delta to a field."""
        word, shift = self.pos[(name, idx)]
        if word == 0:
            return hi, lo + (delta << shift)
        return hi + (delta << shift), lo

    def slot_dynamic(self, hi, lo, p):
        """Extract slot p where p is a per-row tensor."""
        out = jnp.zeros_like(lo)
        for q in range(self.P):
            out = jnp.where(p == q, self.get(hi, lo, "slot", q), out)
        return out

    def add_slot_dynamic(self, hi, lo, p, delta):
        for q in range(self.P):
            h2, l2 = self.add(hi, lo, "slot", q, delta)
            hi = jnp.where(p == q, h2, hi)
            lo = jnp.where(p == q, l2, lo)
        return hi, lo


import os as _os

# optional Pallas bitonic sort for the dedup (correct and ~at parity
# with XLA's variadic sort on v5e; kept opt-in until it wins clearly)
_USE_PALLAS_SORT = _os.environ.get("COMDB2_TPU_PALLAS_SORT") == "1"


def _batch_contig_perm(B, F, R):
    """Row permutation gathering each batch's rows (frontier + P
    candidate chunks, each F-blocked per batch) into contiguous
    (B, R) blocks."""
    idx = jnp.arange(B * R)
    b = idx // R
    rem = idx % R
    c = rem // F
    r = rem % F
    return c * (B * F) + b * F + r


def _k_dedup(hi, lo, valid, inv_hi, inv_lo, B, F, single_word: bool):
    """Sort keys (invalid rows replaced by their batch's sentinel so
    they stay in their block), dedup adjacent, compact per batch."""
    R = hi.shape[0] // B
    h = jnp.where(valid, hi, inv_hi)
    l = jnp.where(valid, lo, inv_lo)
    n_rows = hi.shape[0]
    use_pallas = False
    if (_USE_PALLAS_SORT and not single_word
            and n_rows % B == 0 and (R & (R - 1)) == 0):
        from . import pallas_sort as PS

        use_pallas = PS.sort_pairs_available()   # cached probe
    if use_pallas:
        # per-block bitonic sort in VMEM; validity rides in the keys
        # (sentinels sort to each block's tail), so sorting values
        # directly replaces the argsort+gather pair
        # the per-block sort needs batch-contiguous rows; the concat
        # layout interleaves batches (frontier + P candidate chunks,
        # each F-blocked), so gather into (B, R) blocks first
        perm = _batch_contig_perm(B, F, R)
        hs2, ls2 = PS.sort_pairs(h[perm].reshape(B, R),
                                 l[perm].reshape(B, R))
        hs, ls = hs2.reshape(-1), ls2.reshape(-1)
        # recover validity: valid keys can never equal the sentinel
        # (their invalid bit is clear); sentinel of sorted block b is
        # inv_hi[b*F] (inv_hi is F-blocked by batch)
        sent_h = jnp.repeat(inv_hi[:B * F].reshape(B, F)[:, 0], R)
        sent_l = jnp.repeat(inv_lo[:B * F].reshape(B, F)[:, 0], R)
        va = ~((hs == sent_h) & (ls == sent_l))
    else:
        if single_word:
            order = jnp.argsort(l)
        else:
            order = jnp.lexsort((l, h))
        hs, ls = h[order], l[order]
        va = valid[order]
    pad = jnp.zeros(1, bool)
    same = jnp.concatenate([pad, (hs[1:] == hs[:-1])
                            & (ls[1:] == ls[:-1]) & va[:-1]])
    keep = va & ~same
    c = jnp.cumsum(keep)
    e = c - keep
    row = jnp.arange(hi.shape[0])
    block = row // R
    base = e.reshape(B, R)[:, 0]
    rank = e - base[block]
    n_b = c.reshape(B, R)[:, -1] - base
    target = jnp.where(keep & (rank < F), block * F + rank, B * F)
    out_hi = jnp.zeros(B * F + 1, jnp.int32).at[target].set(hs,
                                                            mode="drop")
    out_lo = jnp.zeros(B * F + 1, jnp.int32).at[target].set(ls,
                                                            mode="drop")
    slot_row = jnp.arange(B * F)
    out_va = (slot_row % F) < jnp.minimum(n_b, F)[slot_row // F]
    return (out_hi[:B * F], out_lo[:B * F], out_va,
            jnp.minimum(n_b, F), n_b > F)


def _k_expand(succ, lay: KeyLayout, hi, lo, valid):
    """Candidate keys: for each pending slot q, linearize it — set the
    slot field to LIN (0) and step the state field. Pure field
    arithmetic; only the succ gather touches memory."""
    s = lay.get(hi, lo, "state")
    c_hi, c_lo, c_va = [], [], []
    for q in range(lay.P):
        tq = lay.get(hi, lo, "slot", q)
        pending = tq >= 2
        s2 = succ[s, jnp.maximum(tq - 2, 0)]
        ok = valid & pending & (s2 >= 0)
        h2, l2 = lay.add(hi, lo, "slot", q, -tq)       # slot -> LIN
        h2, l2 = lay.add(h2, l2, "state", 0, s2 - s)
        c_hi.append(h2)
        c_lo.append(l2)
        c_va.append(ok)
    return (jnp.concatenate(c_hi), jnp.concatenate(c_lo),
            jnp.concatenate(c_va))


def _k_closure(succ, lay, hi, lo, valid, n_b, inv_hi_all, inv_lo_all,
               B, F, max_iter=None):
    P = lay.P
    if max_iter is None:
        max_iter = P + 1

    def cond(c):
        return c[5] & (c[6] < max_iter)

    def body(c):
        hi, lo, va, n, ovf_sticky, _, it = c
        c_hi, c_lo, c_va = _k_expand(succ, lay, hi, lo, va)
        a_hi = jnp.concatenate([hi, c_hi])
        a_lo = jnp.concatenate([lo, c_lo])
        a_va = jnp.concatenate([va, c_va])
        hi2, lo2, va2, n2, ovf = _k_dedup(
            a_hi, a_lo, a_va, inv_hi_all, inv_lo_all, B, F,
            lay.single_word)
        ovf2 = ovf_sticky | ovf
        changed = jnp.any(n2 > n) | jnp.any(ovf)
        return hi2, lo2, va2, n2, ovf2, changed, it + 1

    init = body((hi, lo, valid, n_b, jnp.zeros(B, bool),
                 jnp.bool_(True), jnp.int32(0)))
    hi, lo, va, n, ovf, _, _ = lax.while_loop(cond, body, init)
    return hi, lo, va, n, ovf


@functools.partial(jax.jit, static_argnames=("B", "F", "P", "n_states",
                                             "n_transitions"))
def check_device_keys(succ, inv_proc, inv_tr, ok_proc, depth, *,
                      B: int, F: int, P: int, n_states: int,
                      n_transitions: int):
    """The key-packed batch engine: B histories, frontier = (hi, lo)
    int32 pairs, one sort per closure iteration.

    seg arrays: inv_proc/inv_tr (S, B, K), ok_proc (S, B), depth (S,);
    returns per-lane (status[B], fail_segment[B], n_final[B]). Requires
    :class:`KeyLayout` to fit."""
    lay = KeyLayout(B, n_states, n_transitions, P)
    assert lay.fits, "key layout must fit 62 bits"
    S, _, K = inv_proc.shape
    rows = jnp.arange(B * F, dtype=jnp.int32)
    batch = rows // F

    # per-row constants: the batch field and the invalid sentinel
    bword, bshift = lay.pos[("batch", 0)]
    ivword, ivshift = lay.pos[("invalid", 0)]
    zero = jnp.zeros_like(rows)
    if bword == 1:
        base_hi, base_lo = batch << bshift, zero
    else:
        base_hi, base_lo = zero, batch << bshift
    inv_hi_row = base_hi + ((1 << ivshift) if ivword == 1 else 0)
    inv_lo_row = base_lo + ((1 << ivshift) if ivword == 0 else 0)
    # candidates inherit row i -> frontier row i // P... but expansion
    # concatenates per-q chunks: candidate chunk q holds rows 0..B*F in
    # order, so its batch layout equals the frontier's, tiled P times
    inv_hi_all = jnp.concatenate([inv_hi_row] * (P + 1))
    inv_lo_all = jnp.concatenate([inv_lo_row] * (P + 1))

    # initial frontier: one empty config per batch (all slots IDLE=1)
    idle_lo = 0
    idle_hi = 0
    for q in range(P):
        w, sh = lay.pos[("slot", q)]
        if w == 0:
            idle_lo |= 1 << sh
        else:
            idle_hi |= 1 << sh
    hi0 = base_hi + idle_hi
    lo0 = base_lo + idle_lo
    valid0 = (jnp.arange(B * F) % F) == 0

    def step(carry, seg):
        hi, lo, va, n_b, status, fail_at = carry
        inv_p, inv_t, ok_p, sidx, depth = seg

        live_b = (status == VALID) & (ok_p >= 0)
        live_row = live_b[batch]

        h, l = hi, lo
        for k in range(K):
            p_row = inv_p[batch, k]
            tr_row = inv_t[batch, k]
            m = live_row & (p_row >= 0)
            # slot p: IDLE (1) -> tr+2; delta = tr+1
            h2, l2 = lay.add_slot_dynamic(h, l, jnp.maximum(p_row, 0),
                                          tr_row + 1)
            h = jnp.where(m, h2, h)
            l = jnp.where(m, l2, l)

        h2, l2, va2, n2, ovf = _k_closure(succ, lay, h, l, va, n_b,
                                          inv_hi_all, inv_lo_all, B, F,
                                          max_iter=depth)
        okp_row = jnp.maximum(ok_p, 0)[batch]
        slot_ok = lay.slot_dynamic(h2, l2, okp_row)
        returned = va2 & (slot_ok == 0)                 # LIN
        h3, l3 = lay.add_slot_dynamic(h2, l2, okp_row,
                                      jnp.where(returned, 1, 0))
        n3 = jnp.sum(returned.reshape(B, F), axis=1)

        st_new = jnp.where(ovf, UNKNOWN,
                           jnp.where(n3 == 0, INVALID, VALID)
                           ).astype(jnp.int32)
        status2 = jnp.where(live_b, st_new, status)
        fail2 = jnp.where(live_b & (st_new != VALID), sidx, fail_at)
        keep_row = live_row & (status2[batch] == VALID)
        hi_o = jnp.where(keep_row, h3, hi)
        lo_o = jnp.where(keep_row, l3, lo)
        va_o = jnp.where(keep_row, returned, va)
        n_o = jnp.where(live_b & (status2 == VALID), n3, n_b)
        return (hi_o, lo_o, va_o, n_o, status2, fail2), None

    carry = (hi0, lo0, valid0, jnp.ones(B, jnp.int32),
             jnp.full(B, VALID, jnp.int32), jnp.full(B, -1, jnp.int32))
    segs = (inv_proc, inv_tr, ok_proc, jnp.arange(S, dtype=jnp.int32),
            depth)
    (hi, lo, va, n_b, status, fail_at), _ = lax.scan(step, carry, segs)
    return status, fail_at, n_b


# --- batched (independent histories) ---------------------------------------

#: sharded keys dispatches this process — one per
#: :func:`check_device_keys_sharded` call (ONE fused program covering
#: every shard), counted the way ``txn.closure_jax.DISPATCHES`` is
DISPATCHES = 0

@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_batch(succ, kind, proc, tr, *, F: int, P: int,
                       n_states=None, n_transitions=None):
    """TEST ORACLE — the event-level search vmapped over dense step
    streams (B, n_pad) from :func:`make_stream`, sharing one successor
    table. The batch path runs on segment tensors instead
    (``checker.batch``); this is the independent device cross-check
    the tests compare it against."""
    bits = _bits_for(n_states, n_transitions, P)
    fn = functools.partial(_check_impl, succ, F=F, P=P, bits=bits)
    return jax.vmap(fn)(kind, proc, tr)


@functools.lru_cache(maxsize=64)
def _sharded_keys_fn(mesh, batch_axis: str, B: int, F: int, P: int,
                     n_states: int, n_transitions: int):
    """One NAMED jitted shard_map program per (mesh, shape) class —
    the compile-surface guard keys observed lowerings by jit name, and
    an eagerly-applied shard_map would log an anonymous wrapper (same
    reason ``txn.closure_jax._jitted`` uses a named wrapper). The
    per-shard body is the keys engine at B/D."""
    from jax.sharding import PartitionSpec as PS

    D = mesh.shape[batch_axis]
    fn = functools.partial(check_device_keys, B=B // D, F=F, P=P,
                           n_states=n_states, n_transitions=n_transitions)
    sm = jax.shard_map(
        lambda s, ip, it, op, dp: fn(s, ip, it, op, dp),
        mesh=mesh,
        in_specs=(PS(), PS(None, batch_axis, None),
                  PS(None, batch_axis, None), PS(None, batch_axis),
                  PS()),
        out_specs=(PS(batch_axis), PS(batch_axis), PS(batch_axis)),
        # no collectives anywhere in the engines — each shard is a
        # closed computation, so the varying-axis bookkeeping check
        # (which trips on scan carries initialized from constants)
        # is unnecessary
        check_vma=False)

    def check_device_keys_sharded(s, ip, it, op, dp):
        return sm(s, ip, it, op, dp)

    return jax.jit(check_device_keys_sharded)


def check_device_keys_sharded(mesh, succ, inv_proc, inv_tr, ok_proc,
                              depth, *, B: int, F: int, P: int,
                              n_states: int, n_transitions: int,
                              batch_axis: str = "batch"):
    """shard_map the keys engine over the mesh's batch axis: each
    device runs its own flat batch of B/D histories — pure data
    parallelism over ICI, zero cross-device collectives (the device
    form of ``independent/checker``'s per-key partitioning,
    ``independent.clj:252-300``; SURVEY §2.5 item 8).

    Round 1 routed every mesh run to the vmapped per-lane engine
    (~20x worse per lane); this keeps the key-packed engine under
    sharding. B must be divisible by the mesh axis size (callers pad
    with sentinel histories — ``checker.batch`` pads B to a pow2
    multiple of D so per-shard shapes stay inside the bucketed
    program inventory)."""
    global DISPATCHES
    D = mesh.shape[batch_axis]
    assert B % D == 0, (B, D)
    fn = _sharded_keys_fn(mesh, batch_axis, B, F, P, n_states,
                          n_transitions)
    DISPATCHES += 1
    return fn(succ, inv_proc, inv_tr, ok_proc, depth)


def check_sharded(mesh, succ, kind, proc, tr, *, F: int, P: int,
                  n_states=None, n_transitions=None,
                  batch_axis: str = "batch"):
    """TEST ORACLE ONLY — the vmap engine sharded over a device mesh.

    Removed from the production batch path (round 7): vmap lowers ~20x
    worse per lane than the key-packed encoding (CLAUDE.md), so
    sharding it scales a pessimized program; ``check_batch`` routes
    mesh traffic through the stream/keys sharded engines instead and
    degrades to the SINGLE-device segmented vmap
    (:func:`check_device_seg_batch`) when nothing else fits. This
    stays as an independent cross-check for the mesh parity suite (a
    second sharded code path with unrelated lowering). The
    ``vmap-sharded-oracle`` analysis rule flags any non-test caller.

    The batch axis rides data parallelism over ICI; each device runs
    whole (sub)histories — no intra-search communication (SURVEY §2.5
    item 8)."""
    from jax.sharding import NamedSharding, PartitionSpec as Pspec
    batch_sh = NamedSharding(mesh, Pspec(batch_axis))
    repl = NamedSharding(mesh, Pspec())
    kind = jax.device_put(kind, batch_sh)
    proc = jax.device_put(proc, batch_sh)
    tr = jax.device_put(tr, batch_sh)
    succ = jax.device_put(succ, repl)
    return check_device_batch(succ, kind, proc, tr, F=F, P=P,
                              n_states=n_states,
                              n_transitions=n_transitions)
