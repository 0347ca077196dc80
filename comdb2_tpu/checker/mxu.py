"""MXU frontier engine — BFS-as-matmul closure for wide-P histories.

The fused Pallas kernel (:mod:`.pallas_seg`) serves P <= 15 and the
two-word key engines cap out around the same width; genuinely
concurrent P >= 16 closures are 2^P frontiers that overflow the XLA
ladder's 65536 cap and come back honest UNKNOWN. This engine converts
that workload class to verdicts, borrowing the tensor-core-BFS pattern
(PAPERS.md: *Graph Traversal on Tensor Cores*, *BLEST*) our txn
matrix-closure engine already proved out (:mod:`comdb2_tpu.txn
.closure_jax`): when the frontier is wide, expansion as structured
matmul beats scalar/sort pipelines.

Design:

- **Configs are bit-packed, never explicit tensors.** A config (state +
  P slots) packs losslessly into ``PackPlan.n_words`` int32 words
  (:class:`~.linear_jax.PackPlan`, the round-4 wide-P key plan whose
  per-word budgets come from ``_greedy_split``). The frontier is W
  word-columns of ``B*F`` rows — at P=30 that is ~5 words/config
  instead of the 31 an explicit ``(F, P)`` slot tensor costs, which is
  what makes frontier capacities past 65536 affordable at all. Slot
  mutation (invoke / linearize / return) is single-word field
  arithmetic; no field straddles a word by the plan's construction.
- **Expansion rides the MXU.** One closure step computes the successor
  state of EVERY (config, transition) pair at once: the frontier's
  one-hot config-by-state incidence ``[B*F, S]`` multiplies the
  successor table's value and validity planes ``[S, T]`` in two bf16
  matmuls with f32 accumulation — exact, the :mod:`~comdb2_tpu.txn
  .closure_jax` trick: operands are 0/1 one-hot rows against entries
  <= ``S_CAP``-1 = 255 (bf16 has 8 mantissa bits; integers to 256 are
  exact) and each output element has exactly one nonzero partial, so
  nothing can cancel or round.  Per-slot candidates then select their
  transition's lane from the ``[B*F, T]`` surface — a lane gather, not
  a 2-D table gather per (config, slot).
- **Dedup stays the exact sort-adjacency lexsort.** Candidate ∪
  frontier rows sort by their packed words plus one extra top key
  ``batch*2 + invalid`` (invalid rows zero their plan words and stay
  inside their batch's block, the :func:`~.linear_jax
  ._flat_dedup_compact` discipline); duplicates are adjacent by
  construction and compact per batch with the fixed-block-count
  arithmetic. Hash-fingerprint ordering stays banned — colliding
  non-identical rows would break adjacency exactly as everywhere else.
- **Capacity escalates in-place.** The chunked entry carries the
  frontier between calls like ``expand_seg_carry``: an overflow widens
  the PREVIOUS chunk boundary's carry to the next ladder rung and
  re-runs only the overflowing chunk. ``CAPACITIES`` tops out at
  131072 — the honest-UNKNOWN threshold for wide P is now 2x the XLA
  ladder's, and only past it does the driver report UNKNOWN.

Shape discipline: F and the memo dims ride the usual pow2 buckets
(``pad_succ``); P is even-bucketed by the driver like the XLA engines;
the batch form's tensors are the same ``(S, B, K)`` family the
keys engine uses, so the serving layer's closed-program-set
rules apply unchanged (PROGRAMS.md `mxu-frontier` site).

Crossover (docs/architecture.md has the arithmetic): below P = 16 the
fused kernel (P <= 15) and the 2-word key engines win — their whole
closure iteration is a handful of vreg ops, while a matmul step pays
the ``[B*F, S]`` one-hot build regardless of frontier occupancy. At
P >= 16 the explicit engines' per-iteration cost scales with P (P
gather/scatter passes, P+2 sort keys) while this engine's matmul is
P-independent and its key count grows only as ceil(bits/31).
"""

from __future__ import annotations

import functools
import os as _os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import linear_jax as LJ

VALID, INVALID, UNKNOWN = LJ.VALID, LJ.INVALID, LJ.UNKNOWN

#: driver crossover: the fused kernel serves P <= 15; this engine owns
#: wider P (bounded in-flight — remap_slots makes P the max CONCURRENT
#: open calls, so any history with bounded in-flight depth qualifies)
MIN_P = 16

#: past this the multi-word sort keys (W ~ P*slot_bits/31) stop paying
#: for themselves; the XLA seg2 ladder still serves such shapes
MAX_P = 32

#: successor-table caps: S_CAP keeps every succ entry <= 255 so ONE
#: bf16 value plane is exact (8 mantissa bits); T_CAP bounds the
#: matmul surface's lane axis
S_CAP = 256
T_CAP = 128

#: frontier ladder (pow2; in-place escalation). The top rung is the
#: new honest-UNKNOWN threshold — 2x the XLA ladder's 65536.
CAPACITIES = (1024, 8192, 131072)

#: single chunk of the chunked driver path (segments per dispatch)
CHUNK = 1024

#: engine dispatches this process, counted at the public entries
#: below (the ``closure_jax.closure_diag`` idiom: jitted cores keep
#: the compile-log names, thin wrappers count) — bench/fuzz scripts
#: assert the one-dispatch-per-call discipline on measured deltas
DISPATCHES = 0


def enabled() -> bool:
    """Escape hatch: ``COMDB2_TPU_MXU=0`` routes wide-P traffic back
    to the XLA ladder (read per call — tests flip it)."""
    return _os.environ.get("COMDB2_TPU_MXU", "1") != "0"


def bucket_F(F: int) -> int:
    """Bucket a caller frontier budget UP to the smallest
    ``CAPACITIES`` rung that holds it (the top rung when none does).
    Every dispatch site must route F through this: the rungs are the
    engine's declared program surface (PROGRAMS.md mxu-frontier F
    axis), and a raw caller F would compile an off-inventory program
    the guard can't see."""
    return next((c for c in CAPACITIES if c >= F), CAPACITIES[-1])


def fits(n_states: int, n_transitions: int, P: int) -> bool:
    """Shape-only capability gate (no driver policy): table inside the
    matmul caps, P inside the key budget, and a lossless
    :class:`~.linear_jax.PackPlan` exists."""
    if P < 1 or P > MAX_P:
        return False
    if n_states > S_CAP or n_transitions > T_CAP:
        return False
    return LJ.make_pack_plan(n_states, n_transitions, P) is not None


def serves(n_states: int, n_transitions: int, P: int) -> bool:
    """Driver policy: the engine owns P >= MIN_P (the fused kernel and
    the 2-word key engines win below the crossover)."""
    return enabled() and P >= MIN_P and fits(n_states, n_transitions, P)


# --- packed-field arithmetic ------------------------------------------------
#
# fields = [state, slot_0, .., slot_{P-1}] at plan.assign positions;
# slot values stored +2 (LIN=-2 -> 0, IDLE=-1 -> 1, pending t -> t+2)
# exactly like _pack_plan_words, so a dedup key here IS the key the
# single-history engines sort by.

def _get(plan, words, fi):
    w, sh = plan.assign[fi]
    width = plan.state_bits if fi == 0 else plan.slot_bits
    return (words[w] >> sh) & ((1 << width) - 1)


def _add(plan, words, fi, delta):
    """Add a (data-dependent) delta to field ``fi``; every mutation
    keeps the field in range, so no borrow can cross fields."""
    w, sh = plan.assign[fi]
    out = list(words)
    out[w] = out[w] + (delta << sh)
    return out


def _get_slot_dyn(plan, words, p):
    """Extract slot ``p`` where ``p`` is a per-row tensor (unrolled
    over the static P — the KeyLayout.slot_dynamic pattern)."""
    out = jnp.zeros_like(words[0])
    for q in range(plan.P):
        out = jnp.where(p == q, _get(plan, words, 1 + q), out)
    return out


def _add_slot_dyn(plan, words, p, delta):
    out = list(words)
    for q in range(plan.P):
        w, sh = plan.assign[1 + q]
        out[w] = out[w] + (jnp.where(p == q, delta, 0) << sh)
    return out


def _idle_words(plan) -> list:
    """Host ints: the packed initial config (state 0, all slots IDLE)."""
    vals = [0] * plan.n_words
    for q in range(plan.P):
        w, sh = plan.assign[1 + q]
        vals[w] |= 1 << sh                       # IDLE stores as 1
    return vals


# --- exact dedup: packed-key lexsort + per-batch block compaction -----------

def _dedup(plan, words, valid, B: int, F: int):
    """Sort rows by (plan words, batch*2+invalid top key — primary);
    duplicates are ADJACENT by exactness of the packed keys; compact
    each batch's survivors into its F-row block. Every chunk of the
    input contributes exactly B*F batch-major rows, so batch b owns
    sorted rows [b*R, (b+1)*R) — the fixed-block-count argument of
    ``_flat_dedup_compact``. Returns (words', valid', n_per_batch[B],
    overflow[B]) at frontier shape (B*F,)."""
    rows = words[0].shape[0]
    R = rows // B
    batch = (jnp.arange(rows, dtype=jnp.int32) % (B * F)) // F
    # invalid rows zero their fields (negative garbage would corrupt
    # the sort) but KEEP their batch id, so per-batch row counts stay
    # fixed; the invalid bit sorts them to their block's tail
    ws = [jnp.where(valid, w, 0) for w in words]
    top = batch * 2 + (~valid).astype(jnp.int32)
    order = jnp.lexsort(tuple(ws) + (top,))
    ws = [w[order] for w in ws]
    tops = top[order]
    va = valid[order]
    pad = jnp.zeros(1, bool)
    eq = tops[1:] == tops[:-1]                   # same batch, both valid
    for w in ws:
        eq = eq & (w[1:] == w[:-1])
    same = jnp.concatenate([pad, eq & va[:-1]])
    keep = va & ~same
    c = jnp.cumsum(keep)
    e = c - keep
    row = jnp.arange(rows)
    block = row // R
    base = e.reshape(B, R)[:, 0]
    rank = e - base[block]
    n_b = c.reshape(B, R)[:, -1] - base
    target = jnp.where(keep & (rank < F), block * F + rank, B * F)
    out = [jnp.zeros(B * F + 1, jnp.int32).at[target].set(w, mode="drop")
           [:B * F] for w in ws]
    slot_row = jnp.arange(B * F)
    out_va = (slot_row % F) < jnp.minimum(n_b, F)[slot_row // F]
    return out, out_va, jnp.minimum(n_b, F), n_b > F


# --- matmul expansion + closure ---------------------------------------------

def _succ_planes(succ):
    """Value and validity planes of the (padded) successor table as
    bf16 matmul operands. Entries are < S_CAP = 256, so the value
    plane is bf16-EXACT on its own (no byte slicing needed)."""
    val = jnp.maximum(succ, 0).astype(jnp.bfloat16)
    ok = (succ >= 0).astype(jnp.bfloat16)
    return val, ok


def _expand_surface(plan, succ_val, succ_ok, words):
    """The MXU step: one-hot config-by-state incidence times the
    successor planes -> per-(config, transition) successor state and
    validity surfaces, ``[rows, T]`` each. Exact: 0/1 one-hot rows,
    entries <= 255, f32 accumulation, exactly one nonzero partial per
    output element (the closure_jax trick)."""
    S = succ_val.shape[0]
    states = _get(plan, words, 0)
    oh = (states[:, None] == jnp.arange(S, dtype=jnp.int32)[None, :]
          ).astype(jnp.bfloat16)
    s2 = jnp.einsum("rs,st->rt", oh, succ_val,
                    preferred_element_type=jnp.float32)
    ok = jnp.einsum("rs,st->rt", oh, succ_ok,
                    preferred_element_type=jnp.float32)
    return s2.astype(jnp.int32), ok > 0.5


def _closure(plan, succ_val, succ_ok, words, valid, n_b, B: int,
             F: int, max_iter):
    """Fixed point of single-call linearization over the packed
    frontier: MXU expansion, packed-key dedup, sticky per-batch
    overflow, the exact pending-depth iteration bound."""
    P = plan.P

    def cond(c):
        return c[4] & (c[5] < max_iter)

    def body(c):
        ws, va, n, ovf_sticky, _, it = c
        s2_all, ok_all = _expand_surface(plan, succ_val, succ_ok, ws)
        states = _get(plan, ws, 0)
        cand_ws = [[w] for w in ws]
        cand_va = [va]
        for q in range(P):
            tq = _get(plan, ws, 1 + q)           # stored encoding
            pending = tq >= 2
            t_id = jnp.maximum(tq - 2, 0)
            s2 = jnp.take_along_axis(s2_all, t_id[:, None],
                                     axis=1)[:, 0]
            okq = jnp.take_along_axis(ok_all, t_id[:, None],
                                      axis=1)[:, 0]
            w2 = _add(plan, ws, 1 + q, -tq)      # slot -> LIN (0)
            w2 = _add(plan, w2, 0, s2 - states)
            for i in range(plan.n_words):
                cand_ws[i].append(w2[i])
            cand_va.append(va & pending & okq)
        all_ws = [jnp.concatenate(cw) for cw in cand_ws]
        all_va = jnp.concatenate(cand_va)
        ws2, va2, n2, ovf = _dedup(plan, all_ws, all_va, B, F)
        ovf2 = ovf_sticky | ovf                  # truncation is final
        # an overflowed batch can never recover a trustworthy verdict
        # (its frontier is truncated and the verdict is pinned UNKNOWN
        # by the sticky flag) — excluding it from the progress test
        # stops the loop re-running full expansion+lexsort passes on
        # the ladder rungs that exist to overflow before escalation
        changed = jnp.any((n2 > n) & ~ovf2)
        return ws2, va2, n2, ovf2, changed, it + 1

    init = body((words, valid, n_b, jnp.zeros(B, bool),
                 jnp.bool_(True), jnp.int32(0)))
    ws, va, n, ovf, _, _ = lax.while_loop(cond, body, init)
    return ws, va, n, ovf


# --- the segment step --------------------------------------------------------

def _make_step(plan, succ_val, succ_ok, B: int, F: int, K: int):
    rows = jnp.arange(B * F, dtype=jnp.int32)
    batch = rows // F

    def step(carry, seg):
        words, va, n_b, status, fail_at = carry
        inv_p, inv_t, ok_p, sidx, depth = seg    # (B,K),(B,K),(B,),(),()

        live_b = (status == VALID) & (ok_p >= 0)
        live_row = live_b[batch]

        ws = list(words)
        for k in range(K):                       # K static, unrolled
            p_row = inv_p[batch, k]
            tr_row = inv_t[batch, k]
            m = live_row & (p_row >= 0)
            col = jnp.maximum(p_row, 0)
            cur = _get_slot_dyn(plan, ws, col)
            # absolute set (slot -> tr+2), like the XLA engines
            ws = _add_slot_dyn(plan, ws, col,
                               jnp.where(m, tr_row + 2 - cur, 0))

        ws2, va2, _n2, ovf = _closure(plan, succ_val, succ_ok, ws, va,
                                      n_b, B, F, depth)
        okp_row = jnp.maximum(ok_p, 0)[batch]
        slot_ok = _get_slot_dyn(plan, ws2, okp_row)
        returned = va2 & (slot_ok == 0)          # LIN
        ws3 = _add_slot_dyn(plan, ws2, okp_row,
                            jnp.where(returned, 1, 0))   # LIN -> IDLE
        n3 = jnp.sum(returned.reshape(B, F), axis=1)

        st_new = jnp.where(ovf, UNKNOWN,
                           jnp.where(n3 == 0, INVALID, VALID)
                           ).astype(jnp.int32)
        status2 = jnp.where(live_b, st_new, status)
        fail2 = jnp.where(live_b & (st_new != VALID), sidx, fail_at)
        keep_row = live_row & (status2[batch] == VALID)
        words_o = tuple(jnp.where(keep_row, a, b)
                        for a, b in zip(ws3, words))
        va_o = jnp.where(keep_row, returned, va)
        n_o = jnp.where(live_b & (status2 == VALID), n3, n_b)
        return (words_o, va_o, n_o, status2, fail2), None

    return step


@functools.partial(jax.jit, static_argnames=("P", "n_states",
                                             "n_transitions"))
def pending_histogram(words, valid, *, P: int, n_states: int,
                      n_transitions: int):
    """Per-config pending-call counts bucketed on device (the MXU form
    of :func:`~.linear_jax.pending_histogram`): only P+1 ints are read
    back per progress tick, never the packed frontier."""
    plan = _plan_for(n_states, n_transitions, P)
    pend = jnp.zeros_like(words[0])
    for q in range(P):
        pend = pend + (_get(plan, words, 1 + q) >= 2).astype(jnp.int32)
    return jnp.bincount(pend, weights=valid.astype(jnp.int32),
                        length=P + 1)


def _plan_for(n_states: int, n_transitions: int, P: int):
    assert n_states <= S_CAP and n_transitions <= T_CAP, \
        (n_states, n_transitions, "outside the MXU table caps")
    plan = LJ.make_pack_plan(n_states, n_transitions, P)
    assert plan is not None, "no lossless PackPlan for this shape"
    return plan


def init_carry(B: int, F: int, P: int, n_states: int,
               n_transitions: int):
    """Host-side initial carry (numpy — the chunked entry takes it as
    a real input and the jit transfers it; eager device_puts here
    would cost host-device round-trips): one empty config per batch, all
    slots IDLE."""
    plan = _plan_for(n_states, n_transitions, P)
    idle = _idle_words(plan)
    words = tuple(np.full(B * F, v, np.int32) for v in idle)
    valid = np.zeros(B * F, bool)
    valid[::F] = True
    return (words, valid, np.ones(B, np.int32),
            np.full(B, VALID, np.int32), np.full(B, -1, np.int32))


def _device_carry(plan, B: int, F: int):
    """The same initial carry built inside the trace (broadcasts, not
    baked B*F-row literal constants)."""
    idle = _idle_words(plan)
    words = tuple(jnp.full(B * F, v, jnp.int32) for v in idle)
    valid = (jnp.arange(B * F) % F) == 0
    return (words, valid, jnp.ones(B, jnp.int32),
            jnp.full(B, VALID, jnp.int32), jnp.full(B, -1, jnp.int32))


def expand_carry(carry, F_new: int):
    """Widen a GOOD chunk-boundary carry to a larger capacity — the
    in-place escalation of ``expand_seg_carry``: resume at the
    overflowing chunk instead of restarting the history. B is
    recovered from the status row; each batch's F-block pads in
    place. Status/fail reset — the carry must predate the overflow."""
    words, valid, n_b, status, fail = carry
    words = tuple(np.asarray(w) for w in words)
    valid = np.asarray(valid)
    B = np.asarray(status).shape[0]
    F_old = valid.shape[0] // B
    pad = F_new - F_old
    if pad < 0:
        raise ValueError("carry wider than target capacity")
    words = tuple(
        np.pad(w.reshape(B, F_old), ((0, 0), (0, pad))).reshape(-1)
        for w in words)
    valid = np.pad(valid.reshape(B, F_old),
                   ((0, 0), (0, pad))).reshape(-1)
    return (words, valid, np.asarray(n_b),
            np.full(B, VALID, np.int32), np.full(B, -1, np.int32))


def _scan(succ, inv_proc, inv_tr, ok_proc, depth, carry, seg_offset,
          B: int, F: int, P: int, n_states: int, n_transitions: int):
    plan = _plan_for(n_states, n_transitions, P)
    succ_val, succ_ok = _succ_planes(succ)
    S, _, K = inv_proc.shape
    segs = (inv_proc, inv_tr, ok_proc,
            seg_offset + jnp.arange(S, dtype=jnp.int32), depth)
    step = _make_step(plan, succ_val, succ_ok, B, F, K)
    carry2, _ = lax.scan(step, carry, segs)
    return carry2


@functools.partial(jax.jit, static_argnames=("B", "F", "P", "n_states",
                                             "n_transitions"))
def check_device_mxu_batch(succ, inv_proc, inv_tr, ok_proc, depth, *,
                           B: int, F: int, P: int, n_states: int,
                           n_transitions: int):
    """The batched MXU engine: B histories, packed-word frontier,
    matmul expansion. Same tensors and outputs as
    :func:`~.linear_jax.check_device_keys` — seg arrays
    inv_proc/inv_tr (S, B, K), ok_proc (S, B); returns per-batch
    ``(status[B], fail_segment[B], n_final[B])``."""
    carry = _device_carry(_plan_for(n_states, n_transitions, P), B, F)
    _, _, n_b, status, fail_at = _scan(
        succ, inv_proc, inv_tr, ok_proc, depth, carry, jnp.int32(0),
        B, F, P, n_states, n_transitions)
    return status, fail_at, n_b


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_mxu(succ, inv_proc, inv_tr, ok_proc, depth, *,
                     F: int, P: int, n_states: int,
                     n_transitions: int):
    """Single-history form (the driver's non-chunked path): seg arrays
    as :func:`~.linear_jax.check_device_seg` takes them; returns
    scalar ``(status, fail_segment, n_final)``."""
    S, K = inv_proc.shape
    st, fa, n = _batch_jit(
        succ, inv_proc.reshape(S, 1, K), inv_tr.reshape(S, 1, K),
        ok_proc.reshape(S, 1), depth, B=1, F=F, P=P,
        n_states=n_states, n_transitions=n_transitions)
    return st[0], fa[0], n[0]


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_mxu_chunk(succ, inv_proc, inv_tr, ok_proc, depth,
                           seg_offset, carry, *, F: int, P: int,
                           n_states: int, n_transitions: int):
    """One chunk of the single-history search (B=1 carry from
    :func:`init_carry` / :func:`expand_carry`); ``seg_offset`` biases
    the segment indices recorded in ``fail_at``. The driver escalates
    in place: on UNKNOWN it widens the PRE-chunk carry with
    :func:`expand_carry` and re-runs only this chunk."""
    S, K = inv_proc.shape
    return _scan(succ, inv_proc.reshape(S, 1, K),
                 inv_tr.reshape(S, 1, K), ok_proc.reshape(S, 1),
                 depth, carry, seg_offset, 1, F, P, n_states,
                 n_transitions)


@functools.partial(jax.jit, static_argnames=("F", "P", "n_states",
                                             "n_transitions"))
def check_device_mxu_megabatch(succs, inv_proc, inv_tr, ok_proc,
                               depth, seg_offset, carries, *, F: int,
                               P: int, n_states: int,
                               n_transitions: int):
    """B session-lanes of the chunk form fused into ONE program (the
    stream megabatch, docs/streaming.md "Megabatched advance"):
    ``succs``/``carries`` are B-tuples (each session owns its memo
    table and resident B=1 carry), delta tensors are lane-major
    ``(B, S, K)`` / ``(B, S)``, ``seg_offset`` is ``(B,)``. The lane
    body IS the chunk scan — vmap of its deterministic integer ops is
    elementwise-identical to B solo dispatches (padding lanes and
    dead ``ok_proc=-1`` segments select the old carry), so the fused
    carries are bit-equal to the per-session path. Returns a B-tuple
    of updated carries."""
    plan = _plan_for(n_states, n_transitions, P)
    succ_b = jnp.stack(succs)
    carry_b = jax.tree.map(lambda *xs: jnp.stack(xs), *carries)

    def lane(succ_l, ip, it, okp, dp, off, carry):
        sv, so = _succ_planes(succ_l)
        S, K = ip.shape
        segs = (ip.reshape(S, 1, K), it.reshape(S, 1, K),
                okp.reshape(S, 1),
                off + jnp.arange(S, dtype=jnp.int32), dp)
        step = _make_step(plan, sv, so, 1, F, K)
        carry2, _ = lax.scan(step, carry, segs)
        return carry2

    out = jax.vmap(lane)(succ_b, inv_proc, inv_tr, ok_proc, depth,
                         seg_offset, carry_b)
    return tuple(jax.tree.map(lambda x: x[i], out)
                 for i in range(len(carries)))


# --- counted public entries -------------------------------------------------
#
# The jitted cores above keep the public names — the compile log (and
# so the compile-surface guard) keys programs by the jit name — and the
# module attributes are rebound to thin wrappers that count DISPATCHES
# (the ``closure_jax.closure_diag`` idiom), so bench/fuzz deltas
# measure real engine dispatches, not call-site bookkeeping.

_batch_jit = check_device_mxu_batch
_single_jit = check_device_mxu
_chunk_jit = check_device_mxu_chunk
_megabatch_jit = check_device_mxu_megabatch


def check_device_mxu_batch(succ, inv_proc, inv_tr, ok_proc, depth, *,
                           B: int, F: int, P: int, n_states: int,
                           n_transitions: int):
    """Counted dispatch of the batched engine (jitted core above)."""
    global DISPATCHES
    DISPATCHES += 1
    return _batch_jit(succ, inv_proc, inv_tr, ok_proc, depth, B=B,
                      F=F, P=P, n_states=n_states,
                      n_transitions=n_transitions)


def check_device_mxu(succ, inv_proc, inv_tr, ok_proc, depth, *,
                     F: int, P: int, n_states: int,
                     n_transitions: int):
    """Counted dispatch of the single-history engine (core above)."""
    global DISPATCHES
    DISPATCHES += 1
    return _single_jit(succ, inv_proc, inv_tr, ok_proc, depth, F=F,
                       P=P, n_states=n_states,
                       n_transitions=n_transitions)


def check_device_mxu_chunk(succ, inv_proc, inv_tr, ok_proc, depth,
                           seg_offset, carry, *, F: int, P: int,
                           n_states: int, n_transitions: int):
    """Counted dispatch of the chunk engine (jitted core above)."""
    global DISPATCHES
    DISPATCHES += 1
    return _chunk_jit(succ, inv_proc, inv_tr, ok_proc, depth,
                      seg_offset, carry, F=F, P=P, n_states=n_states,
                      n_transitions=n_transitions)


def check_device_mxu_megabatch(succs, inv_proc, inv_tr, ok_proc,
                               depth, seg_offset, carries, *, F: int,
                               P: int, n_states: int,
                               n_transitions: int):
    """Counted dispatch of the fused session-lane engine (core
    above) — ONE program regardless of lane count."""
    global DISPATCHES
    DISPATCHES += 1
    return _megabatch_jit(succs, inv_proc, inv_tr, ok_proc, depth,
                          seg_offset, carries, F=F, P=P,
                          n_states=n_states,
                          n_transitions=n_transitions)


__all__ = ["CAPACITIES", "CHUNK", "DISPATCHES", "MAX_P", "MIN_P",
           "S_CAP", "T_CAP", "check_device_mxu",
           "check_device_mxu_batch", "check_device_mxu_chunk",
           "check_device_mxu_megabatch", "enabled", "expand_carry",
           "fits", "init_carry", "serves"]
