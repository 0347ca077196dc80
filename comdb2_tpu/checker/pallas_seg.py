"""Fully-fused Pallas TPU engine for the segmented frontier search.

The XLA engines (:mod:`.linear_jax`) express one closure iteration as
~40 small device ops; on a 50k-op history the per-op fixed overhead —
not the arithmetic — dominates (measured ~45 us/iteration on v5e while
the same data fits one vector register). This engine instead runs the
ENTIRE segment loop inside one Pallas kernel per 1024-segment chunk:

- The frontier is an ``(8, 128)`` int32 key-pair buffer — exactly one
  vreg per word — living in VMEM scratch that persists across the
  sequential grid. Row 0 holds the F=128 config frontier; rows 1..P
  hold the P candidate chunks of a closure expansion (hence P <= 7).
- A config is a packed (hi, lo) key, fields as in
  ``linear_jax.KeyLayout``: P slot fields (0=linearized, 1=idle,
  t+2=pending transition t) then the state field. Invalid lanes hold a
  sentinel (hi = 1<<30) that sorts after every valid key.
- Dedup = full 1024-lane bitonic sort over the flattened buffer (55
  compare-exchange stages, each ~a dozen single-vreg VPU ops), duplicate
  marking via neighbour compare, then a second sort to compact
  survivors into row 0. Exact, like the XLA engines' sort-adjacency
  dedup — never hash-fingerprint ordering.
- The memoized successor table rides in VMEM as a flat (8, 128) block;
  ``succ[s, t]`` is an unrolled row-broadcast + per-lane
  ``take_along_axis`` gather (Mosaic supports same-shape lane gathers),
  so the whole model step stays in-kernel. Tables up to 4096 entries
  ride a (32, 128) VMEM block (bucketed to bound recompiles).
- The segment stream (ok_proc, depth, invokes) is a scalar-prefetch
  array; SMEM bounds it to ~1.5k segments per call, so the host jits a
  ``lax.scan`` over 1024-segment chunks, carrying the frontier buffers
  and (status, fail, n) between calls.

Semantics match ``check_device_seg`` exactly: per ok-op segment, apply
invokes, run the linearization closure at most ``depth`` iterations
(stopping at a fixed point), keep configs whose ok-slot linearized,
empty frontier => INVALID at that segment, more unique configs than
the top rung holds => UNKNOWN (the reference's OOM-abort contract,
``linear.clj:318-326``).

Frontier rungs. Rung 0 is the buffer's row 0 (F = 128) and serves
almost every segment. The wide rungs, r = 1..WIDE_RUNGS, hold
``128 << r`` configs (256 .. 4096) in a block-structured VMEM
scratch: the frontier in its first ``2**r`` rows, candidate chunk q
in the next ``2**r`` rows each, sorted by a bitonic network over
(8,128) blocks (per-block passes, then cross-block compare-exchanges
at a runtime block count) — still the exact sort-adjacency dedup.
Rung handover: the closure is monotone (S_{j+1} = S_j ∪ expand(S_j)),
and a rung's sort buffer holds the frontier and all P candidate
chunks, so a rung that overflows at iteration k has the whole union
S_{k+1} deduped in hand. It carries that union, compacted, to the
smallest rung that holds it, and the closure goes on there at
iteration k+1; nothing is redone from the segment's entry frontier.
Past the top rung the segment is UNKNOWN. The next segment returns to
rung 0 as soon as its frontier fits 128. The rung sizes are runtime
values, so every rung shares one compiled kernel; a frontier wider
than 128 crosses chunk calls in the carry's extra ``WIDE_ROWS`` rows.
Falls back unavailable (see :func:`spec_for`) when P, the key budget,
or the table don't fit — the driver then uses the XLA engines.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

from ..obs import trace as _obs
from ..utils import next_pow2 as _next_pow2

ROWS, LANES = 8, 128
N = ROWS * LANES          # flat sort width
F = LANES                 # rung-0 frontier capacity (row 0)
WIDE_RUNGS = 5            # wide rungs r = 1..5: 128 << r configs
F_TOP = F << WIDE_RUNGS   # the top rung's capacity (4096)
WIDE_ROWS = F_TOP // LANES        # carry rows of a wide frontier
WIDE_BLOCKS = WIDE_ROWS // ROWS   # ... in (8,128) blocks
#: stat-row lanes: segments finished on rung r (r = 1..WIDE_RUNGS) at
#: STAT_RUNGS + r - 1, segments past the top rung at STAT_PAST_TOP,
#: closed unions handed to a wider rung at STAT_HANDOVERS and the
#: closure iterations they carried (not redone) at STAT_ITERS_CARRIED
STAT_RUNGS = 4
STAT_PAST_TOP = STAT_RUNGS + WIDE_RUNGS
STAT_HANDOVERS = STAT_PAST_TOP + 1
STAT_ITERS_CARRIED = STAT_PAST_TOP + 2
#: per-history counters: the stat lanes STAT_RUNGS .. STAT_ITERS_CARRIED
N_COUNTERS = STAT_ITERS_CARRIED - STAT_RUNGS + 1
#: stat-row lane: invoke-only rows scanned so far (see INVOKE_ONLY)
STAT_INV = STAT_ITERS_CARRIED + 1
K_MAX = 8                 # invokes per kernel row (SMEM row width)
CHUNK = 1024              # segments per kernel call. SMEM-bounded in
                          # TWO ways: the scalar-prefetch array
                          # (~14336 int32) AND a per-grid-step SMEM
                          # cost (~500 B/step toward the 1 MB space) —
                          # a 2048-step grid fails Mosaic compile with
                          # "Exceeded smem capacity" even at width 4,
                          # while 1408 steps compile. 1024 is the
                          # known-good cap; raising it bought ~noise
                          # (+1.5% on the 50k bench, within run-to-run
                          # variance) before hitting the wall.
CHUNK_INTERPRET = 16      # interpret mode unrolls the grid at trace
                          # time — a 1024-step chunk would trace 1024
                          # kernel bodies
MAX_STREAM_B = 2048       # histories per streamed call (VMEM-bounded:
                          # two (B,128) int32 result blocks = 2 MB)

SENT_HI = np.int32(1 << 30)
SENT_LO = np.int32(0)

# status codes (match linear_jax)
VALID, INVALID, UNKNOWN = 0, 1, 2


MAX_TABLE = 8 * N          # successor-table entries the kernel serves
                           # (64 VMEM rows; the gather unrolls per
                           # row, so big tables pay compile+run cost
                           # only in specs that need them)

import os as _os

# Pallas interpret mode: runs the kernel as plain XLA ops — the only
# way to execute the kernel's exact semantics on the CPU test mesh
# (Mosaic is TPU-only). Enabled explicitly by CPU tests and the CPU
# mesh dryrun (use_interpret), never from the environment and never on
# a TPU: per-spec interpret compiles cost ~40 s each on CPU, and on
# the chip it would hide the kernel behind an XLA emulation of it.
_INTERPRET = False


def interpret_active() -> bool:
    return _INTERPRET


def use_interpret(on: bool = True) -> None:
    """Toggle interpret mode; clears the compiled-call and
    availability caches (specs differ: interpret chunks are short).
    Refused on a TPU backend, where the real kernel must run."""
    global _INTERPRET
    if _INTERPRET == on:
        return
    if on:
        from ..utils import platform

        if platform.on_tpu():
            raise RuntimeError("Pallas interpret mode is for CPU "
                               "validation; the TPU runs the kernel")
    _INTERPRET = on
    _chunk_call.cache_clear()
    _chunk_jit.cache_clear()
    _scan_fn.cache_clear()
    _sharded_scan_fn.cache_clear()
    _reset_fn.cache_clear()
    _CARRY_POOL.clear()
    available.cache_clear()


# Carry donation (continuous-batching round): the streamed scan's
# carry buffers (frontier words, stat row, results block) are marked
# donate_argnums so XLA aliases them into the scan outputs instead of
# allocating a second copy, and finished carries are RECYCLED through
# a per-(spec, b_pad, device) pool — the next dispatch of a hot bucket
# re-initializes the previous dispatch's device buffers with a tiny
# on-device ``carry_reset`` program instead of re-uploading initial
# values host-to-device. Gated (env or use_carry_donation)
# so the donated and non-donated paths can be bit-compared.
_DONATE = _os.environ.get("COMDB2_TPU_DONATE_CARRIES", "1") != "0"

#: carries re-initialized on device instead of re-uploaded — the
#: serving metrics mirror this next to MOSAIC_BUILDS
CARRY_REUSES = 0

#: recycled (ws_tuple, stat) carry sets per (spec, b_pad, device) —
#: bounded per key; entries are device arrays from finished dispatches
_CARRY_POOL: dict = {}
_CARRY_POOL_CAP = 4


def donation_active() -> bool:
    return _DONATE


def use_carry_donation(on: bool = True) -> None:
    """Toggle carry donation + pooling (the parity tests compare the
    two paths bit-for-bit). Disabling drops the pooled device buffers;
    the jitted scan variants are cached per flag, so no recompiles."""
    global _DONATE
    if _DONATE == on:
        return
    _DONATE = on
    _CARRY_POOL.clear()


class SegKernelSpec(NamedTuple):
    """Static key layout + table geometry for one compiled kernel.

    Deliberately does NOT carry the exact (n_states, n_transitions):
    the table stride is a runtime scalar and ``table_rows`` is
    pow2-bucketed, so all memo shapes with the same log-scale field
    widths share ONE compiled kernel. Per-shape Mosaic compiles are
    slow and can OOM LLVM (CLAUDE.md); production ``analysis()`` loops
    see many slightly-different shapes (ADVICE r1)."""
    P: int                 # slot count (<= rows - 1)
    K: int                 # max invokes per segment
    slot_bits: int
    state_bits: int
    # (word, shift) per slot q, and for the state field; word 0 is the
    # LEAST significant sort key, word n_words-1 the most significant
    slot_pos: tuple
    state_pos: tuple
    table_rows: int        # pow2 bucket of ceil(S*T / LANES)
    chunk: int             # segments per kernel call (SMEM-bounded)
    table_rows_pad: int    # table buffer rows (bucketed: 8 or 32)
    rows: int              # buffer rows: 8 (P<=7) or 16 (P<=15)
    n_words: int           # int32 key words per config (2 or 3)


def spec_for(n_states: int, n_transitions: int, P: int,
             K: int) -> Optional[SegKernelSpec]:
    """Build the static spec, or None when this shape can't run in the
    fused kernel (caller falls back to the XLA engines).

    P <= 7 runs the classic (8,128) one-vreg-per-word geometry; P <= 15
    a (16,128) buffer (candidate chunks live in rows 1..P) with up to
    THREE key words — the round-3 VERDICT #2 extension that serves the
    reference register test's concurrency 10 (comdb2/core.clj:567-613)
    on the production kernel."""
    if K > 8:
        return None
    rows = ROWS if P <= ROWS - 1 else 2 * ROWS
    if P > rows - 1:
        return None
    if n_states * n_transitions > MAX_TABLE:
        return None
    slot_bits = max(int(np.ceil(np.log2(max(n_transitions + 2, 2)))), 1)
    state_bits = max(int(np.ceil(np.log2(max(n_states, 2)))), 1)
    pos = []
    word, shift = 0, 0
    for width in [slot_bits] * P + [state_bits]:
        if width > 29:
            return None
        if shift + width > 31:
            word, shift = word + 1, 0
        pos.append((word, shift))
        shift += width
    # the most significant word must keep bits 29/30 free (the okp
    # flag has no kernel analog, but the sentinel 1<<30 must sort
    # after every valid key); spill to a fresh word when the last
    # field crosses bit 30
    n_words = word + 1
    if shift > 30:
        n_words += 1
    if n_words > 3:
        return None
    table_rows = _next_pow2(-(-(n_states * n_transitions) // LANES))
    table_rows_pad = (ROWS if table_rows <= ROWS
                      else (4 * ROWS if table_rows <= 4 * ROWS
                            else 8 * ROWS))
    # SMEM holds the scalar-prefetch stream: keep chunk * width under
    # ~56KB (measured limit ~60KB on v5e), in multiples of 128
    width = 2 + 2 * K
    chunk = min(CHUNK, (14336 // width) // 128 * 128)
    if _INTERPRET:
        chunk = CHUNK_INTERPRET
    return SegKernelSpec(P, K, slot_bits, state_bits,
                         tuple(pos[:P]), pos[P],
                         table_rows, chunk, table_rows_pad,
                         rows, n_words)


#: small-delta chunk rungs for the STREAMING kernel rung only (the
#: batch/driver path always scans full chunks): a 16-op append on a
#: spec.chunk=1024 program pays the whole 1024-step grid — these
#: spec._replace(chunk=...) variants keep the carry geometry (rows,
#: n_words are chunk-independent) while shrinking the grid, at the
#: price of at most len(STREAM_CHUNKS) extra Mosaic builds per base
#: spec. Closed ladder: PROGRAMS.md stream-delta declares them.
STREAM_CHUNKS = (64, 256)


def delta_spec(spec: SegKernelSpec, n_segments: int) -> SegKernelSpec:
    """The smallest declared chunk rung serving an ``n_segments``
    delta (the base spec when none is smaller — interpret mode's
    chunk=16 already undercuts the ladder and passes through)."""
    for c in STREAM_CHUNKS:
        if c >= n_segments and c < spec.chunk:
            return spec._replace(chunk=c)
    return spec


def pack_table(succ: np.ndarray, rows: int = ROWS) -> np.ndarray:
    """Flatten the successor table into a (rows, 128) int32 block
    (row-major, padded with -1)."""
    flat = np.full(rows * LANES, -1, np.int32)
    flat[:succ.size] = np.ascontiguousarray(succ, np.int32).reshape(-1)
    return flat.reshape(rows, LANES)


def carry_rows(spec: SegKernelSpec) -> int:
    """Rows of each key-word carry: the (rows,128) rung-0 buffer, then
    the ``WIDE_ROWS`` rows a wider frontier crosses chunk calls in."""
    return spec.rows + WIDE_ROWS


def scratch_rows(spec: SegKernelSpec) -> tuple:
    """Rows of the kernel's VMEM scratch per key word: the (rows,128)
    rung-0 buffer, the wide frontier (the top rung's ``WIDE_ROWS``),
    and the top rung's sort buffer."""
    return (spec.rows, WIDE_ROWS,
            rung_blocks(spec.P, WIDE_RUNGS) * ROWS)


def rung_blocks(P: int, r: int) -> int:
    """(8,128) blocks of rung ``r``'s sort buffer: the frontier's
    ``2**r`` rows and P candidate chunks of as many, padded to a power
    of two for the bitonic network."""
    return _next_pow2(-(-((P + 1) << r) // ROWS))


def initial_frontier(spec: SegKernelSpec):
    """List of ``n_words`` (carry_rows,128) host arrays (least-
    significant word first): lane 0 of row 0 = the empty config (all
    slots idle, state 0), everything else sentinel."""
    ws = [np.full((carry_rows(spec), LANES),
                  SENT_HI if w == spec.n_words - 1 else SENT_LO,
                  np.int32)
          for w in range(spec.n_words)]
    for w, v in enumerate(_root_key(spec)):
        ws[w][0, 0] = v
    return ws


def _init_stat() -> np.ndarray:
    """Initial (1, 128) stat row: [status, fail, n, hist-counter] in
    lanes 0..3, then the per-history counters (rung segments, past the
    top, handovers, iterations carried: STAT_RUNGS ..
    STAT_ITERS_CARRIED) and the invoke-only rows scanned (STAT_INV),
    all zero — the layout the kernel's sstat load/flush assumes."""
    stat0 = np.zeros((1, LANES), np.int32)
    stat0[0, 0] = VALID
    stat0[0, 1] = -1
    stat0[0, 2] = 1
    stat0[0, 3] = -1
    return stat0


# --- kernel body helpers (traced; all shapes static) ------------------------
#
# Keys are lists ``ws`` of int32 word planes, least-significant word
# FIRST (ws[-1] is the primary sort key and carries the sentinel).

def _iotas(rows: int = ROWS):
    import jax.numpy as jnp
    from jax import lax

    row = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return row, lane, row * LANES + lane


def _fetch(x, j, lane, rows: int = ROWS):
    """Values at flat positions f+j and f-j (circular over the
    (rows,128) row-major order). j is a static power of two."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    if j % LANES == 0:
        r = j // LANES
        return (pltpu.roll(x, rows - r, 0), pltpu.roll(x, r, 0))
    a = pltpu.roll(x, LANES - j, 1)          # (i, l) <- (i, (l+j)%128)
    b = pltpu.roll(a, rows - 1, 0)           # <- (i+1, (l+j)%128)
    plus = jnp.where(lane + j < LANES, a, b)
    c = pltpu.roll(x, j, 1)                  # (i, l) <- (i, (l-j)%128)
    d = pltpu.roll(c, 1, 0)                  # <- (i-1, ...)
    minus = jnp.where(lane - j >= 0, c, d)
    return plus, minus


def _ws_less(ws, pws):
    """Lexicographic < of key lists (most significant word last)."""
    less = None
    eq = None
    for w, pw in zip(reversed(ws), reversed(pws)):
        if less is None:
            less = w < pw
            eq = w == pw
        else:
            less = less | (eq & (w < pw))
            eq = eq & (w == pw)
    return less


def _ws_eq(ws, pws):
    eq = None
    for w, pw in zip(ws, pws):
        eq = (w == pw) if eq is None else (eq & (w == pw))
    return eq


def _cmp_exchange(ws, pws, take_min):
    """One bitonic compare-exchange: keep the lexicographic min or max
    of ``ws`` vs the partner ``pws`` per lane."""
    import jax.numpy as jnp

    mine_less = _ws_less(ws, pws)
    return [jnp.where(take_min == mine_less, w, pw)
            for w, pw in zip(ws, pws)]


def _bitonic_stage(ws, j, asc, lane, flat, rows):
    """One bitonic compare-exchange stage at flat distance ``j`` (a
    static power of two): position f pairs with f ^ j and keeps the min
    where ``asc`` says the pair sorts ascending."""
    import jax.numpy as jnp

    is_low = (flat & j) == 0
    pws = []
    for w in ws:
        wp, wm = _fetch(w, j, lane, rows)
        pws.append(jnp.where(is_low, wp, wm))
    return _cmp_exchange(ws, pws, is_low == asc)


def _sort_flat(ws, rows: int = ROWS):
    """Full ascending bitonic sort of the rows*128 flat keys."""
    n = rows * LANES
    _, lane, flat = _iotas(rows)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            asc = (flat & k) == 0 if k < n else (flat >= 0)
            ws = _bitonic_stage(ws, j, asc, lane, flat, rows)
            j //= 2
        k *= 2
    return ws


def _sort_row(ws, rows: int = ROWS):
    """Ascending bitonic sort of the 128 lanes of EVERY row
    independently (lane rolls only — pairs never cross rows). Used by
    the mini tier, where the whole frontier+candidates fit one row."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    _, lane, _ = _iotas(rows)
    k = 2
    while k <= LANES:
        j = k // 2
        while j >= 1:
            is_low = (lane & j) == 0
            asc = (lane & k) == 0 if k < LANES else (lane >= 0)
            pws = [jnp.where(is_low, pltpu.roll(w, LANES - j, 1),
                             pltpu.roll(w, j, 1)) for w in ws]
            ws = _cmp_exchange(ws, pws, is_low == asc)
            j //= 2
        k *= 2
    return ws


def _mini_width(P: int) -> int:
    """Frontier size served by the single-row tier: the 128 lanes
    split into P+1 equal groups (frontier + one per candidate chunk) —
    e.g. 42 configs at P=2, 18 at P=6, 11 at P=10."""
    return LANES // (P + 1)


def _sentinel(ws, cond):
    """Replace keys where ``cond`` with the sentinel."""
    import jax.numpy as jnp

    out = [jnp.where(cond, SENT_LO, w) for w in ws[:-1]]
    out.append(jnp.where(cond, SENT_HI, ws[-1]))
    return out


def _dedup_count_row(ws, rows: int):
    """Row-0 dedup after a row sort: sentinel the duplicate neighbours,
    count unique valid keys in row 0."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    row, lane, _ = _iotas(rows)
    prev = [pltpu.roll(w, 1, 1) for w in ws]
    valid = ws[-1] < SENT_HI
    dup = valid & _ws_eq(ws, prev) & (lane > 0)
    keep = valid & ~dup
    n = jnp.sum((keep & (row == 0)).astype(jnp.int32))
    return _sentinel(ws, ~keep), n


def _mini_expand(spec, table, stride, ws):
    """Single-row expansion: frontier in lanes 0..M-1 of row 0
    (M = _mini_width(P)); candidate chunk q lands at lanes
    [M*(q+1), M*(q+2)). All rows compute in lockstep; only row 0 is
    meaningful. ``stride`` is the runtime table row stride
    (= the model's exact n_transitions)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    M = _mini_width(spec.P)
    _, lane, _ = _iotas(spec.rows)
    group = lane // M
    fvalid = (ws[-1] < SENT_HI) & (lane < M)
    s = _field(spec, ws, spec.state_pos, spec.state_bits)
    sbase = s * stride               # loop-invariant row base
    out = list(ws)
    for q in range(spec.P):
        tq = _field(spec, ws, spec.slot_pos[q], spec.slot_bits)
        pending = tq >= 2
        idx = sbase + jnp.maximum(tq - 2, 0)
        s2 = _gather_table(table, idx, spec.table_rows, spec.rows)
        ok = fvalid & pending & (s2 >= 0)
        cand = _field_add(spec, ws, spec.slot_pos[q], -tq)
        cand = _field_add(spec, cand, spec.state_pos, s2 - s)
        cand = _sentinel(cand, ~ok)
        m = group == q + 1
        out = [jnp.where(m, pltpu.roll(c, M * (q + 1), 1), o)
               for c, o in zip(cand, out)]
    pad = group > spec.P           # unused groups when P < rows-1
    return _sentinel(out, pad)


def _dedup_count(ws, rows: int):
    """After a sort: mark duplicate neighbours, return (ws', n) with
    dups sentinelled and n = number of unique valid keys."""
    import jax.numpy as jnp

    _, lane, flat = _iotas(rows)
    # previous element = fetch at flat position -1
    prev = [_fetch(w, 1, lane, rows)[1] for w in ws]
    valid = ws[-1] < SENT_HI
    dup = valid & _ws_eq(ws, prev) & (flat > 0)
    keep = valid & ~dup
    n = jnp.sum(keep.astype(jnp.int32))
    return _sentinel(ws, ~keep), n


def _field(spec, ws, pos, bits):
    word, sh = pos
    return (ws[word] >> sh) & ((1 << bits) - 1)


def _field_add(spec, ws, pos, delta):
    """Add a (vector) delta into a field; caller guarantees the field
    stays in range so no borrow crosses field boundaries."""
    word, sh = pos
    out = list(ws)
    out[word] = out[word] + (delta << sh)
    return out


def _gather_table(table, idx, table_rows, rows: int = ROWS):
    """Flat-indexed gather from a (table_rows_pad, 128) block:
    out[e] = table_flat[idx[e]], idx < table_rows*128. Unrolled
    row-broadcast + lane gather."""
    import jax.numpy as jnp

    out = jnp.full((rows, LANES), -1, jnp.int32)
    r = idx >> 7
    c = idx & 127
    for rr in range(table_rows):
        rowv = jnp.broadcast_to(table[rr:rr + 1, :], (rows, LANES))
        g = jnp.take_along_axis(rowv, c, axis=1)
        out = jnp.where(r == rr, g, out)
    return out


def _expand(spec, table, stride, ws):
    """Rows 1..P <- candidates (slot q of each frontier config
    linearized), rows P+1.. <- sentinel. Row 0 (the frontier) is kept.
    ``stride`` is the runtime table row stride."""
    import jax.numpy as jnp

    row, _, _ = _iotas(spec.rows)
    f = [jnp.broadcast_to(w[0:1, :], (spec.rows, LANES)) for w in ws]
    fvalid = f[-1] < SENT_HI
    s = _field(spec, f, spec.state_pos, spec.state_bits)
    sbase = s * stride               # loop-invariant row base
    out = list(ws)
    for q in range(spec.P):
        tq = _field(spec, f, spec.slot_pos[q], spec.slot_bits)
        pending = tq >= 2
        idx = sbase + jnp.maximum(tq - 2, 0)
        s2 = _gather_table(table, idx, spec.table_rows, spec.rows)
        ok = fvalid & pending & (s2 >= 0)
        cand = _field_add(spec, f, spec.slot_pos[q], -tq)
        cand = _field_add(spec, cand, spec.state_pos, s2 - s)
        cand = _sentinel(cand, ~ok)
        m = row == (q + 1)
        out = [jnp.where(m, c, o) for c, o in zip(cand, out)]
    return _sentinel(out, row > spec.P)


def _slot_field_runtime(spec, ws, p):
    """Extract slot p where p is a runtime scalar (unrolled select)."""
    import jax.numpy as jnp

    out = jnp.zeros_like(ws[0])
    for q in range(spec.P):
        out = jnp.where(p == q,
                        _field(spec, ws, spec.slot_pos[q],
                               spec.slot_bits),
                        out)
    return out


def _slot_add_runtime(spec, ws, p, delta, mask):
    """Add delta to slot p (runtime scalar) on lanes where mask."""
    import jax.numpy as jnp

    for q in range(spec.P):
        cand = _field_add(spec, ws, spec.slot_pos[q], delta)
        m = mask & (p == q)
        ws = [jnp.where(m, c, w) for c, w in zip(cand, ws)]
    return ws


def _sort_block(ws, base):
    """Bitonic passes k = 2..1024 over one (8,128) block whose first
    flat position is ``base`` (a runtime multiple of 1024): ascending
    where bit 10 of ``base`` is clear, descending where it is set, so
    neighbouring blocks form the bitonic runs the cross-block passes
    of :func:`_build_kernel`'s ``sort_blocks`` merge."""
    _, lane, flat = _iotas(ROWS)
    pos = flat + base
    k = 2
    while k <= N:
        j = k // 2
        while j >= 1:
            ws = _bitonic_stage(ws, j, (pos & k) == 0, lane, flat, ROWS)
            j //= 2
        k *= 2
    return ws


def _merge_block(ws, asc):
    """The in-block stages (j = 512..1) of one bitonic pass over a
    block whose direction ``asc`` is one (broadcast) bool vector."""
    _, lane, flat = _iotas(ROWS)
    j = N // 2
    while j >= 1:
        ws = _bitonic_stage(ws, j, asc, lane, flat, ROWS)
        j //= 2
    return ws


def _tile_rows(ws, m1):
    """Row i of each word <- row (i & m1) (``m1`` = rows - 1 of a
    frontier narrower than a block, a runtime scalar; 7 keeps the
    block as it is)."""
    import jax.numpy as jnp

    row, _, _ = _iotas(ROWS)
    sel = row & m1
    out = []
    for w in ws:
        t = w
        for rr in range(ROWS):
            t = jnp.where(sel == rr,
                          jnp.broadcast_to(w[rr:rr + 1, :],
                                           (ROWS, LANES)), t)
        out.append(t)
    return out


def _apply_invokes(spec, ws, seg_ref, i, mask):
    """Row ``i``'s invokes (slot p, transition tr): slot p of every
    valid key under ``mask`` goes IDLE(1) -> tr+2 (delta tr+1)."""
    K = spec.K
    for k in range(K):
        p = seg_ref[i, 2 + k]
        tr = seg_ref[i, 2 + K + k]
        m = mask & (ws[-1] < SENT_HI) & (p >= 0)
        ws = _slot_add_runtime(spec, ws, p, tr + 1, m)
    return ws


RESET = -2     # ok_proc marker: flush current history, start the next
#: ok_proc marker of a row that only applies its invokes: a segment
#: with more than K_MAX invokes rides several rows, all but the last
#: invoke-only (closing nothing, so they change no verdict). Rows are
#: numbered in segments by leaving them out (STAT_INV counts them).
INVOKE_ONLY = -3


def kernel_K(K: int) -> int:
    """The invoke width of the kernel rows for segments of up to ``K``
    invokes: K itself up to K_MAX, K_MAX past it (the packers split
    wider segments into invoke-only rows)."""
    return min(K, K_MAX)


def _kernel_rows(segs, K: int, chunk: int = 0):
    """``(ok_proc, depth, inv_proc, inv_tr)`` of ``segs`` with at most
    ``K`` invokes per row: a wider segment's invokes are packed left
    and spread over invoke-only rows ahead of the row that closes it.
    With ``chunk``, a split segment's rows never straddle a chunk
    boundary (invoke-only rows with no invokes fill up to it), so a
    chunk boundary always falls between whole segments."""
    ip, it = segs.inv_proc, segs.inv_tr
    if ip.shape[1] <= K:
        return segs.ok_proc, segs.depth, ip, it
    order = np.argsort(ip < 0, axis=1, kind="stable")
    ip = np.take_along_axis(ip, order, 1)
    it = np.take_along_axis(it, order, 1)
    extra = np.maximum((ip >= 0).sum(1) - 1, 0) // K
    pad = np.zeros_like(extra)
    if chunk:
        start = np.arange(len(extra)) + np.cumsum(extra) - extra
        shift = 0
        for s_ in np.flatnonzero(extra):
            first = start[s_] + shift
            if first // chunk != (first + extra[s_]) // chunk:
                pad[s_] = chunk - first % chunk
                shift += pad[s_]
    pos = np.cumsum(extra + pad + 1) - 1        # each segment's closing row
    total = int(pos[-1]) + 1 if len(pos) else 0
    okp = np.full(total, INVOKE_ONLY, np.int32)
    dep = np.zeros(total, np.int32)
    out_ip = np.full((total, K), -1, np.int32)
    out_it = np.zeros((total, K), np.int32)
    okp[pos] = segs.ok_proc
    dep[pos] = segs.depth
    s_i, c_i = np.nonzero(ip >= 0)
    rows_ = pos[s_i] - extra[s_i] + c_i // K
    out_ip[rows_, c_i % K] = ip[s_i, c_i]
    out_it[rows_, c_i % K] = it[s_i, c_i]
    return okp, dep, out_ip, out_it


def _root_key(spec):
    """Per-word ints (least significant first) of the empty config
    (all slots IDLE, state 0)."""
    words = [0] * spec.n_words
    for q in range(spec.P):
        w, sh = spec.slot_pos[q]
        words[w] |= 1 << sh
    return words


def _build_kernel(spec: SegKernelSpec):
    """The chunk kernel. Grid = (chunk,); scalar-prefetch args:
    seg[chunk, 2+2K] (ok_proc, depth, inv_proc.., inv_tr..) and
    off[2] (global segment offset, table row stride). Inputs: n_words
    key-word carries (carry_rows,128), carry_stat (1,128) [status,
    fail, n, hist-counter, rung counters], results (B_pad, 128), table
    (rows,128). Outputs: the same carries.

    A segment with ok_proc == RESET is a history boundary in a
    multi-history stream: the current history's (status, fail, n) row
    and rung counters are stored at results[counter], the counter
    advances, and the frontier/status reset to the initial state.
    Single-history runs simply have no RESET segments and ignore the
    results buffer.

    Rungs: a segment whose entry frontier fits F runs on rung 0, the
    (rows,128) buffer's row 0. When its closure overflows at iteration
    k, the deduped union S_{k+1} is already sorted compact in the
    buffer: the same grid step hands it to the wide frontier and goes
    on at iteration k+1 on the smallest wide rung that holds it, and a
    wide rung that overflows hands its union on the same way. A
    frontier left wider than F stays in the wide scratch, and the next
    segment starts there."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    P, W, rows = spec.P, spec.n_words, spec.rows
    R = WIDE_RUNGS
    NB = [rung_blocks(P, r) for r in range(R + 1)]
    FB = [max(1, (1 << r) // ROWS) for r in range(R + 1)]
    LOG_NB = NB[R].bit_length() - 1

    root = _root_key(spec)
    # SMEM slots past the rung-0 ones (0..6): the iteration the wide
    # rungs start or go on at (> 0: a handed-over union waits in the
    # wide frontier), continue (the last iteration grew the union),
    # count, ok-count, the rung that holds the segment (R + 1: past the
    # top), the invoke-only rows scanned, then the N_COUNTERS
    # per-history counters from S_CNT
    S_IT, S_CONT, S_NCUR, S_NOK, S_RUNG, S_INV = range(7, 13)
    S_CNT = 13
    S_HAND = S_CNT + STAT_HANDOVERS - STAT_RUNGS
    S_CARRIED = S_CNT + STAT_ITERS_CARRIED - STAT_RUNGS

    def _at(b):
        o = b * ROWS
        return pl.ds(o if isinstance(o, int)
                     else pl.multiple_of(o, ROWS), ROWS)

    def _sel(table, r):
        v = jnp.int32(table[0])
        for rr in range(1, len(table)):
            v = jnp.where(r == rr, jnp.int32(table[rr]), v)
        return v

    def _sent_blocks(n_rows):
        return [jnp.full((n_rows, LANES),
                         SENT_HI if w == W - 1 else SENT_LO, jnp.int32)
                for w in range(W)]

    def kernel(seg_ref, off_ref, *refs):
        # refs: W word carries in, st_in, res_in, tab_ref,
        #       W word carries out, st_out, res_out,
        #       W rung-0 VMEM word scratch, W wide frontier,
        #       W wide sort buffer, sstat SMEM
        ws_in = refs[:W]
        st_in, res_in, tab_ref = refs[W], refs[W + 1], refs[W + 2]
        ws_out = refs[W + 3:2 * W + 3]
        st_out, res_out = refs[2 * W + 3], refs[2 * W + 4]
        wsc = refs[2 * W + 5:3 * W + 5]
        fs = refs[3 * W + 5:4 * W + 5]
        buf = refs[4 * W + 5:5 * W + 5]
        sstat = refs[5 * W + 5]
        i = pl.program_id(0)

        def blk(ref, b):
            return ref[_at(b), :]

        def put(ref, b, v):
            ref[_at(b), :] = v

        def stat_row(lanes):
            """(1,128) row: ``lanes`` {lane: scalar}, then the
            per-history counters; 0 elsewhere."""
            _, lane, _ = _iotas(rows)
            lane = lane[0:1, :]
            out = jnp.zeros((1, LANES), jnp.int32)
            cells = dict(lanes)
            for c in range(N_COUNTERS):
                cells[STAT_RUNGS + c] = sstat[S_CNT + c]
            for ln, v in cells.items():
                out = jnp.where(lane == ln, v, out)
            return out

        def clear_wide():
            sent = _sent_blocks(ROWS)
            for w in range(W):
                for bb in range(WIDE_BLOCKS):
                    put(fs[w], bb, sent[w])

        @pl.when(i == 0)
        def _():
            for w in range(W):
                wsc[w][:] = ws_in[w][0:rows, :]
                fs[w][:] = ws_in[w][rows:rows + WIDE_ROWS, :]
            res_out[:] = res_in[:]
            sstat[0] = st_in[0, 0]      # status
            sstat[1] = st_in[0, 1]      # fail seg (global)
            sstat[2] = st_in[0, 2]      # frontier count
            sstat[6] = st_in[0, 3]      # history counter (stream mode)
            sstat[S_INV] = st_in[0, STAT_INV]
            for c in range(N_COUNTERS):
                sstat[S_CNT + c] = st_in[0, STAT_RUNGS + c]

        ok_p = seg_ref[i, 0]
        depth = seg_ref[i, 1]
        # this row's segment number: invoke-only rows are left out
        # (none on a stream of segments of at most K_MAX invokes)
        seg_no = off_ref[0] + i - sstat[S_INV]

        @pl.when(ok_p == RESET)
        def _():
            row, lane, _ = _iotas(rows)
            cnt = sstat[6]

            @pl.when(cnt >= 0)
            def _():
                res_out[pl.ds(cnt, 1), :] = stat_row(
                    {0: sstat[0], 1: sstat[1], 2: sstat[2]})

            sstat[6] = cnt + 1
            sstat[0] = VALID
            sstat[1] = -1
            sstat[2] = 1
            for c in range(N_COUNTERS):
                sstat[S_CNT + c] = 0
            at_root = (row == 0) & (lane == 0)
            for w in range(W):
                sent = SENT_HI if w == W - 1 else SENT_LO
                wsc[w][:] = jnp.where(at_root, root[w], sent)
            clear_wide()

        live = (sstat[0] == VALID) & (ok_p >= 0)
        wide_in = sstat[2] > F
        sstat[S_IT] = 0

        @pl.when(live & jnp.logical_not(wide_in))
        def _():
            row, lane, _ = _iotas(rows)
            ws = [wsc[w][:] for w in range(W)]
            table = tab_ref[:]
            stride = off_ref[1]      # runtime table row stride
            frow = row == 0
            ws = _apply_invokes(spec, ws, seg_ref, i, frow)

            # --- lazy compaction (round 5): the ok filter no longer
            # sorts survivors forward every segment — the frontier may
            # enter SCATTERED across row 0. The full tier is
            # scatter-proof (masked broadcast); only the mini tier
            # needs the lanes-0..M-1 window, so compact exactly when a
            # mini-sized frontier would otherwise miss it. In mini
            # steady state (frontier stayed within the window) this
            # removes one 28-stage sort per segment.
            M = _mini_width(P)
            extent = jnp.max(jnp.where(
                frow & (ws[-1] < SENT_HI), lane + 1, 0))
            ws = list(lax.cond(
                (sstat[2] <= M) & (extent > M),
                lambda a: tuple(_sort_row(list(a), rows)),
                lambda a: a, tuple(ws)))

            # --- closure: bounded fixed point ------------------------
            # sstat[3]: continue flag, sstat[4]: k + 1 after an
            # overflow at iteration k (0: none), sstat[5]: n
            sstat[3] = 1
            sstat[4] = 0
            sstat[5] = sstat[2]

            def body(it, carry):
                cws = list(carry)

                def run(args):
                    cws = list(args)

                    def full(args):
                        ews = _expand(spec, table, stride, list(args))
                        ews = _sort_flat(ews, rows)
                        ews, n2 = _dedup_count(ews, rows)
                        # flat extent of the deduped survivors: when
                        # they all sit in row 0 already, the next full
                        # iteration (masked row-0 broadcast) needs no
                        # compaction sort
                        _, _, flat = _iotas(rows)
                        ext = jnp.max(jnp.where(ews[-1] < SENT_HI,
                                                flat + 1, 0))
                        return tuple(ews) + (n2, ext)

                    def mini(args):
                        # frontier fits one lane group (128/(P+1)
                        # lanes): the whole iteration stays in row 0
                        # and the sorts are 28 lane-only stages
                        # instead of the full flat ones. Extent LANES+1
                        # forces the (row) compaction: dedup holes may
                        # leave survivors beyond the M-lane window the
                        # next mini read needs.
                        ews = _mini_expand(spec, table, stride,
                                           list(args))
                        ews = _sort_row(ews, rows)
                        ews, n2 = _dedup_count_row(ews, rows)
                        ews = _sentinel(ews, row > 0)
                        return tuple(ews) + (n2,
                                             jnp.int32(LANES + 1))

                    use_mini = sstat[5] <= M
                    out = lax.cond(use_mini, mini, full, tuple(cws))
                    ews, n2, ext = list(out[:W]), out[W], out[W + 1]
                    ovf = (n2 > F).astype(jnp.int32)
                    changed = (n2 > sstat[5]).astype(jnp.int32)
                    # an overflow ends the loop, so this store is
                    # the last one that runs
                    sstat[4] = ovf * (it + 1)
                    sstat[3] = changed & (1 - ovf)
                    sstat[5] = n2

                    def compact2(args):
                        was_mini = args[W]
                        return lax.cond(
                            was_mini,
                            lambda a: tuple(_sort_row(list(a), rows)),
                            lambda a: tuple(_sort_flat(list(a), rows)),
                            args[:W])

                    # no growth => the deduped union IS the previous
                    # frontier; restore it. Growth with every survivor
                    # already in row 0 (full tier, ext <= LANES) =>
                    # skip the compaction sort too — the next full
                    # iteration and the ok filter are both
                    # sentinel-mask-based over row 0
                    need_sort = (changed == 1) & \
                        (use_mini | (ext > LANES))
                    return lax.cond(
                        need_sort, compact2,
                        lambda a: lax.cond(changed == 1,
                                           lambda b: b[:W],
                                           lambda b: tuple(cws),
                                           a),
                        tuple(ews) + (use_mini,))

                return lax.cond(sstat[3] == 1, run, lambda a: a,
                                tuple(cws))

            ws = list(lax.fori_loop(0, depth, body, tuple(ws)))
            closed = ws

            # --- ok filter: keep configs whose ok-slot linearized ----
            tq_ok = _slot_field_runtime(spec, ws, ok_p)
            returned = frow & (ws[-1] < SENT_HI) & (tq_ok == 0)
            # clear the slot back to IDLE (LIN=0 -> +1)
            ws = _slot_add_runtime(spec, ws, ok_p, 1, returned)
            ws = _sentinel(ws, frow & ~returned)
            n2 = jnp.sum(returned.astype(jnp.int32))
            # survivors stay SCATTERED in row 0 — the next segment
            # compacts lazily only if its mini tier needs the window
            # (see the closure-entry cond above); unconditional
            # re-sorting here cost 28 stages on every segment

            def commit():
                st_new = jnp.where(n2 == 0, INVALID, VALID)
                sstat[1] = jnp.where(st_new == VALID, sstat[1],
                                     seg_no)
                sstat[0] = st_new
                sstat[2] = n2
                for w in range(W):
                    wsc[w][:] = ws[w]

            def handover():
                # an overflow at iteration k left the closed union
                # S_{k+1} sorted compact in the buffer (the growth
                # compaction): it becomes the wide frontier, and the
                # wide rungs go on at iteration k+1
                sent = _sent_blocks(WIDE_ROWS - rows)
                for w in range(W):
                    fs[w][0:rows, :] = closed[w]
                    fs[w][rows:WIDE_ROWS, :] = sent[w]
                sstat[S_IT] = sstat[4]
                sstat[S_NCUR] = sstat[5]
                sstat[S_HAND] = sstat[S_HAND] + 1
                sstat[S_CARRIED] = sstat[S_CARRIED] + sstat[4]

            lax.cond(sstat[4] > 0, handover, commit)

        # --- the wide rungs (r = 1..R, 128 << r configs) ---------------
        # The frontier sits compact in the first 2**r rows of ``fs``
        # (positions past its count hold the sentinel); rung r's sort
        # buffer ``buf`` holds it and P candidate chunks of 2**r rows
        # each, NB[r] (8,128) blocks in all.

        def rung_of(n):
            """The smallest wide rung that holds ``n`` configs (R + 1
            past the top)."""
            r = jnp.int32(1)
            for rr in range(1, R + 1):
                r = r + jnp.where(n > (F << rr), 1, 0)
            return r

        def copy_blocks(src, dst, nblk):
            def body(b, c):
                for w in range(W):
                    put(dst[w], b, blk(src[w], b))
                return c
            lax.fori_loop(0, nblk, body, 0)

        def sort_blocks(nb):
            """Ascending bitonic sort of ``buf``'s first ``nb`` blocks
            (a runtime power of two)."""
            def per_block(b, c):
                ws = _sort_block([blk(buf[w], b) for w in range(W)],
                                 b * N)
                for w in range(W):
                    put(buf[w], b, ws[w])
                return c
            lax.fori_loop(0, nb, per_block, 0)
            log_nb = jnp.int32(0)
            for x in range(LOG_NB):
                log_nb = log_nb + jnp.where(nb > (1 << x), 1, 0)

            def dir_of(b, k):
                return (jnp.zeros((ROWS, LANES), jnp.int32)
                        + ((b * N) & k)) == 0

            def merge(lk, c):
                k = jnp.left_shift(jnp.int32(N), lk)

                def cross(s, c2):
                    lj = lk - 1 - s
                    jb = jnp.left_shift(jnp.int32(1), lj)

                    def pair(t, c3):
                        b = (jnp.left_shift(jnp.right_shift(t, lj),
                                            lj + 1) | (t & (jb - 1)))
                        a = [blk(buf[w], b) for w in range(W)]
                        o = [blk(buf[w], b + jb) for w in range(W)]
                        keep = _ws_less(a, o) == dir_of(b, k)
                        for w in range(W):
                            put(buf[w], b, jnp.where(keep, a[w], o[w]))
                            put(buf[w], b + jb,
                                jnp.where(keep, o[w], a[w]))
                        return c3
                    return lax.fori_loop(0, jnp.right_shift(nb, 1),
                                         pair, c2)
                lax.fori_loop(0, lk, cross, 0)

                def within(b, c2):
                    ws = _merge_block([blk(buf[w], b) for w in range(W)],
                                      dir_of(b, k))
                    for w in range(W):
                        put(buf[w], b, ws[w])
                    return c2
                return lax.fori_loop(0, nb, within, c)
            lax.fori_loop(1, log_nb + 1, merge, 0)

        def dedup(nb):
            """Sentinel the duplicates of the sorted ``buf`` and count
            the unique valid keys."""
            _, lane, flat = _iotas(ROWS)

            def body(b, carry):
                prev_blk, n = carry[:W], carry[W]
                ws = [blk(buf[w], b) for w in range(W)]
                prev = [jnp.where(flat == 0,
                                  _fetch(pw, 1, lane, ROWS)[1],
                                  _fetch(w, 1, lane, ROWS)[1])
                        for w, pw in zip(ws, prev_blk)]
                valid = ws[-1] < SENT_HI
                keep = valid & ~(_ws_eq(ws, prev) & ((flat + b * N) > 0))
                out = _sentinel(ws, ~keep)
                for w in range(W):
                    put(buf[w], b, out[w])
                return tuple(ws) + (n + jnp.sum(keep.astype(jnp.int32)),)
            init = tuple(_sent_blocks(ROWS)) + (jnp.int32(0),)
            return lax.fori_loop(0, nb, body, init)[W]

        def expand(r, nb, fb, table, stride):
            """``buf`` <- the frontier (group 0) and, in group q+1,
            each config with slot q linearized; sentinel past group
            P. A frontier narrower than a block is tiled over it."""
            m1 = jnp.minimum(jnp.left_shift(jnp.int32(1), r), ROWS) - 1
            row, _, _ = _iotas(ROWS)

            def body(b, c):
                f = _tile_rows([blk(fs[w], b & (fb - 1))
                                for w in range(W)], m1)
                g = jnp.right_shift(row + b * ROWS, r)
                out = _sentinel(f, g != 0)
                fvalid = f[-1] < SENT_HI
                s = _field(spec, f, spec.state_pos, spec.state_bits)
                sbase = s * stride
                for q in range(P):
                    tq = _field(spec, f, spec.slot_pos[q],
                                spec.slot_bits)
                    idx = sbase + jnp.maximum(tq - 2, 0)
                    s2 = _gather_table(table, idx, spec.table_rows,
                                       ROWS)
                    ok = fvalid & (tq >= 2) & (s2 >= 0)
                    cand = _field_add(spec, f, spec.slot_pos[q], -tq)
                    cand = _field_add(spec, cand, spec.state_pos, s2 - s)
                    cand = _sentinel(cand, ~ok)
                    m = g == q + 1
                    out = [jnp.where(m, cc, o) for cc, o in zip(cand, out)]
                for w in range(W):
                    put(buf[w], b, out[w])
                return c
            lax.fori_loop(0, nb, body, 0)

        def ok_filter(fb, ok_p):
            """``buf``'s first ``fb`` blocks <- the frontier's configs
            whose ok-slot linearized, slot cleared; returns their
            count."""
            def body(b, n):
                ws = [blk(fs[w], b) for w in range(W)]
                tq = _slot_field_runtime(spec, ws, ok_p)
                ret = (ws[-1] < SENT_HI) & (tq == 0)
                ws = _sentinel(_slot_add_runtime(spec, ws, ok_p, 1, ret),
                               ~ret)
                for w in range(W):
                    put(buf[w], b, ws[w])
                return n + jnp.sum(ret.astype(jnp.int32))
            return lax.fori_loop(0, fb, body, jnp.int32(0))

        def wide_invokes():
            def body(b, c):
                ws = _apply_invokes(spec, [blk(fs[w], b) for w in range(W)],
                                    seg_ref, i, True)
                for w in range(W):
                    put(fs[w], b, ws[w])
                return c
            lax.fori_loop(0, WIDE_BLOCKS, body, 0)

        # --- invoke-only rows: apply the invokes, close nothing ------
        inv_only = ok_p == INVOKE_ONLY

        @pl.when(inv_only)
        def _():
            sstat[S_INV] = sstat[S_INV] + 1

        @pl.when(inv_only & (sstat[0] == VALID)
                 & jnp.logical_not(wide_in))
        def _():
            row, _, _ = _iotas(rows)
            ws = _apply_invokes(spec, [wsc[w][:] for w in range(W)],
                                seg_ref, i, row == 0)
            for w in range(W):
                wsc[w][:] = ws[w]

        @pl.when(inv_only & (sstat[0] == VALID) & wide_in)
        def _():
            wide_invokes()

        @pl.when(live & (wide_in | (sstat[S_IT] > 0)))
        def _():
            table = tab_ref[:]
            stride = off_ref[1]

            @pl.when(wide_in)
            def _():
                wide_invokes()
                sstat[S_NCUR] = sstat[2]

            sstat[S_RUNG] = rung_of(sstat[S_NCUR])
            sstat[S_CONT] = 1

            def rung(r, c):
                @pl.when(sstat[S_RUNG] == r)
                def _():
                    nb, fb = _sel(NB, r), _sel(FB, r)
                    cap = jnp.left_shift(jnp.int32(F), r)

                    def step(it, c2):
                        # iterations up to depth-1: the closure, until a
                        # fixed point or an overflow, which hands the
                        # union to a wider rung; iteration depth: the
                        # ok filter, on the rung that held
                        here = sstat[S_RUNG] == r
                        closure = here & (it < depth) & \
                            (sstat[S_CONT] == 1)
                        final = here & (it == depth)

                        @pl.when(closure)
                        def _():
                            expand(r, nb, fb, table, stride)

                        @pl.when(final)
                        def _():
                            sstat[S_NOK] = ok_filter(fb, ok_p)

                        nbx = jnp.where(final, fb, nb)

                        def sort_pass(ps, c3):
                            # pass 0 sorts (then dedups, in the
                            # closure); pass 1 compacts a grown union,
                            # the one handed over included
                            @pl.when(((ps == 0) & (closure | final))
                                     | ((ps == 1) & closure
                                        & (sstat[S_CONT] == 1)))
                            def _():
                                sort_blocks(nbx)

                            @pl.when((ps == 0) & closure)
                            def _():
                                n2 = dedup(nb)
                                sstat[S_CONT] = (n2 > sstat[S_NCUR]).astype(
                                    jnp.int32)
                                sstat[S_NCUR] = n2
                                # an overflow moves the segment to the
                                # smallest rung that holds n2 (R + 1:
                                # past the top), at iteration it + 1
                                r2 = jnp.where(n2 > cap, rung_of(n2), r)
                                sstat[S_RUNG] = r2
                                sstat[S_IT] = it + 1
                                hand = ((r2 != r) & (r2 <= R)).astype(
                                    jnp.int32)
                                sstat[S_HAND] = sstat[S_HAND] + hand
                                sstat[S_CARRIED] = sstat[S_CARRIED] + \
                                    hand * (it + 1)
                            return c3
                        lax.fori_loop(0, 2, sort_pass, 0)

                        @pl.when((closure & (sstat[S_CONT] == 1)
                                  & (sstat[S_RUNG] <= R)) | final)
                        def _():
                            copy_blocks(buf, fs, _sel(FB, sstat[S_RUNG]))
                        return c2
                    lax.fori_loop(sstat[S_IT], depth + 1, step, 0)
                return c
            lax.fori_loop(1, R + 1, rung, 0)

            done = sstat[S_RUNG] <= R

            @pl.when(jnp.logical_not(done))
            def _():
                # past the top rung: UNKNOWN, as rung 0 alone said
                sstat[0] = UNKNOWN
                sstat[1] = seg_no
                sstat[2] = sstat[S_NCUR]
                sstat[S_CNT + R] = sstat[S_CNT + R] + 1

            @pl.when(done)
            def _():
                n2 = sstat[S_NOK]
                st_new = jnp.where(n2 == 0, INVALID, VALID)
                sstat[1] = jnp.where(st_new == VALID, sstat[1],
                                     seg_no)
                sstat[0] = st_new
                sstat[2] = n2
                ix = S_CNT + sstat[S_RUNG] - 1
                sstat[ix] = sstat[ix] + 1
                row, _, _ = _iotas(rows)
                sent = _sent_blocks(rows)

                # back to rung 0 once the frontier fits it (its row 0
                # <- block 0's first row); else rung 0's buffer is
                # cleared, so one of the two always holds the frontier
                @pl.when(n2 <= F)
                def _():
                    for w in range(W):
                        wsc[w][:] = jnp.where(
                            row == 0,
                            jnp.broadcast_to(fs[w][0:1, :],
                                             (rows, LANES)),
                            sent[w])
                    clear_wide()

                @pl.when(n2 > F)
                def _():
                    for w in range(W):
                        wsc[w][:] = sent[w]

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            for w in range(W):
                ws_out[w][0:rows, :] = wsc[w][:]
                ws_out[w][rows:rows + WIDE_ROWS, :] = fs[w][:]
            st_out[:] = stat_row({0: sstat[0], 1: sstat[1],
                                  2: sstat[2], 3: sstat[6],
                                  STAT_INV: sstat[S_INV]})

    return kernel


#: fused-kernel programs built this process — one Mosaic compile per
#: distinct (spec, b_pad); the compile-surface guard diffs it the way
#: bench_txn diffs closure_jax.DISPATCHES (utils/compile_guard.py)
MOSAIC_BUILDS = 0

#: streamed-kernel dispatches this process: one per
#: :func:`stream_dispatch` (single device) and one per
#: :func:`stream_dispatch_sharded` (ONE fused dispatch covering every
#: shard of a slice). The mesh parity suite and bench_multichip assert
#: the single-dispatch-per-shard-per-slice discipline on it.
DISPATCHES = 0


@functools.lru_cache(maxsize=32)
def _chunk_call(spec: SegKernelSpec, b_pad: int = 8):
    """b_pad: rows of the per-history results buffer (multi-history
    streams); single-history runs pass a dummy 8-row buffer."""
    global MOSAIC_BUILDS
    MOSAIC_BUILDS += 1
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _build_kernel(spec)
    W, rows = spec.n_words, spec.rows
    c_rows = carry_rows(spec)
    word_spec = pl.BlockSpec((c_rows, LANES), lambda i, *s: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(spec.chunk,),
        in_specs=[word_spec] * W + [
            pl.BlockSpec((1, LANES), lambda i, *s: (0, 0)),
            pl.BlockSpec((b_pad, LANES), lambda i, *s: (0, 0)),
            pl.BlockSpec((spec.table_rows_pad, LANES),
                         lambda i, *s: (0, 0)),
        ],
        out_specs=[word_spec] * W + [
            pl.BlockSpec((1, LANES), lambda i, *s: (0, 0)),
            pl.BlockSpec((b_pad, LANES), lambda i, *s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((n, LANES), jnp.int32)
                        for n in scratch_rows(spec) for _ in range(W)]
        + [pltpu.SMEM((32,), jnp.int32)])

    word_shape = jax.ShapeDtypeStruct((c_rows, LANES), jnp.int32)

    def call(seg, off, ws, stat, res, table):
        """``ws`` is the list/tuple of word carries; returns
        (ws_out_tuple, stat, res)."""
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            name="seg_frontier",
            out_shape=[word_shape] * W + [
                jax.ShapeDtypeStruct((1, LANES), jnp.int32),
                jax.ShapeDtypeStruct((b_pad, LANES), jnp.int32)],
            interpret=_INTERPRET,
        )(seg, off, *ws, stat, res, table)
        return tuple(out[:W]), out[W], out[W + 1]

    return call


def pack_segments(segs, spec: SegKernelSpec) -> np.ndarray:
    """SegmentStream -> (n_chunks, chunk, 2+2K) scalar array, padded
    with dead segments (ok_proc = -1). Segments wider than ``spec.K``
    ride invoke-only rows (:func:`_kernel_rows`)."""
    K, chunk = spec.K, spec.chunk
    okp, dep, ip, it = _kernel_rows(segs, K, chunk)
    S = okp.shape[0]
    n_chunks = max(-(-S // chunk), 1)
    W = 2 + 2 * K
    out = np.zeros((n_chunks, chunk, W), np.int32)
    out[:, :, 0] = -1
    flat = out.reshape(n_chunks * chunk, W)
    flat[:S, 0] = okp
    flat[:S, 1] = dep
    k_in = ip.shape[1]
    flat[:S, 2:2 + k_in] = ip
    flat[:S, 2 + K:2 + K + k_in] = it
    if k_in < K:
        flat[:S, 2 + k_in:2 + K] = -1
    return out


@functools.lru_cache(maxsize=32)
def _scan_fn(spec: SegKernelSpec, b_pad: int = 8,
             stream: bool = False, donate: bool = False):
    """Jitted scan over chunk calls. ``stream=False`` short-circuits
    dead chunks once the (single) history failed; stream mode always
    runs every chunk (later histories are still live) and threads the
    per-history results buffer through the scan. ``donate`` marks the
    carry buffers (ws0/stat0/res0) donated — XLA aliases them into the
    scan outputs instead of holding both copies live; callers must not
    reuse the donated input arrays (``stream_dispatch`` builds or
    recycles them fresh per call)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    call = _chunk_call(spec, b_pad)

    def run(seg_chunks, ws0, stat0, res0, table, stride):
        n_chunks = seg_chunks.shape[0]

        def step(carry, x):
            ws, stat, res = carry
            seg, off = x

            def live(_):
                return call(seg, off, ws, stat, res, table)

            if stream:
                out = live(None)
            else:
                out = lax.cond(stat[0, 0] == VALID, live,
                               lambda _: (ws, stat, res), None)
            return out, None

        starts = (jnp.arange(n_chunks, dtype=jnp.int32)
                  * jnp.int32(spec.chunk)).reshape(n_chunks, 1)
        offs = jnp.concatenate(
            [starts, jnp.full((n_chunks, 1), jnp.int32(stride))], axis=1)
        (ws, stat, res), _ = lax.scan(
            step, (tuple(ws0), stat0, res0), (seg_chunks, offs))
        return ws, stat, res

    # the jit's name names the program in the compile log and in the
    # profiler's "XLA Modules" line (jit_seg_scan)
    run.__name__ = "seg_stream_scan" if stream else "seg_scan"
    if donate:
        return jax.jit(run, donate_argnums=(1, 2, 3))
    return jax.jit(run)


def check_device_pallas(succ: np.ndarray, segs, *, n_states: int,
                        n_transitions: int, P: int):
    """Run the fused-kernel search. Returns (status, fail_seg, n) as
    Python ints, or None when the shape can't run fused."""
    import jax.numpy as jnp

    prep = _prepare(succ, segs, n_states, n_transitions, P)
    if prep is None:
        return None
    spec, seg_chunks, ws0, stat0, table = prep
    run = _scan_fn(spec)
    with _obs.span("pallas.scan", dispatches=1, readbacks=1):
        res0 = jnp.zeros((8, LANES), jnp.int32)  # unused: no RESETs
        _, stat, _ = run(jnp.asarray(seg_chunks), tuple(ws0), stat0,
                         res0, table, n_transitions)
        stat = np.asarray(stat)
    note_rungs(stat)
    return int(stat[0, 0]), int(stat[0, 1]), int(stat[0, 2])


@functools.lru_cache(maxsize=32)
def _chunk_jit(spec: SegKernelSpec):
    """One chunk call as its own program, named ``seg_chunk`` in the
    compile log and the profiler's trace."""
    import jax

    call = _chunk_call(spec)

    def seg_chunk(seg, off, ws, stat, res, table):
        return call(seg, off, ws, stat, res, table)

    return jax.jit(seg_chunk)


def pack_stream(segs_list, spec: SegKernelSpec):
    """Concatenate per-history segment streams into one chunked stream
    with RESET markers: [R][h0][R][h1]...[R]. The first R starts
    history 0 (the counter begins at -1, so nothing is flushed); each
    later R flushes the previous history; the trailing R flushes the
    last. Returns (chunks[n,chunk,W], starts[B]) where starts[b] is
    history b's first segment's global stream index."""
    B = len(segs_list)
    W = 2 + 2 * spec.K
    rows = [_kernel_rows(s, spec.K) for s in segs_list]
    sizes = [r[0].shape[0] for r in rows]
    total = sum(sizes) + B + 1
    chunk = spec.chunk
    n_chunks = max(-(-total // chunk), 1)
    flat = np.zeros((n_chunks * chunk, W), np.int32)
    flat[:, 0] = -1                       # default: dead padding
    starts = np.zeros(B, np.int64)
    pos = skipped = 0
    for b, (okp, dep, ip, it) in enumerate(rows):
        flat[pos, 0] = RESET
        pos += 1
        # in segment numbering: the kernel leaves invoke-only rows out
        starts[b] = pos - skipped
        S = sizes[b]
        k_in = ip.shape[1]
        flat[pos:pos + S, 0] = okp
        flat[pos:pos + S, 1] = dep
        flat[pos:pos + S, 2:2 + k_in] = ip
        if k_in < spec.K:
            flat[pos:pos + S, 2 + k_in:2 + spec.K] = -1
        flat[pos:pos + S, 2 + spec.K:2 + spec.K + k_in] = it
        pos += S
        skipped += int(np.count_nonzero(okp == INVOKE_ONLY))
    flat[pos, 0] = RESET                  # trailing flush
    return flat.reshape(n_chunks, chunk, W), starts


def check_device_pallas_stream(succ: np.ndarray, segs_list, *,
                               n_states: int, n_transitions: int,
                               P: int, devices=None):
    """Check MANY independent histories as one streamed kernel scan —
    the device form of ``independent/checker``'s per-key partitioning
    (``independent.clj:252-300``). One dispatch for the whole batch;
    per-history verdicts come back in the results buffer. Returns a
    list of (status, fail_seg_local, n) or None when the shape can't
    run fused. Every history gets its own verdict: one history's
    INVALID/UNKNOWN never stops the others (the RESET marker restores
    a live frontier).

    A row-parallel tier (8 history streams per kernel scan, one per
    buffer row) lived here through round 4 (commit b57bf53) and was
    REMOVED in round 5: it measured strictly slower on v5e at every
    real shape (256x800-event batch 73k -> 58k ops/s; 4096x2k
    97k -> 76k) because the lockstep closure iterates to the MAX depth
    of the 8 co-scheduled segments, per-row SMEM bookkeeping costs as
    much as the vector work at these shapes, and mini-frontier
    (M=128/(P+1)) overflows pay a second full-width pass — structural
    costs, not tuning gaps (round-4 VERDICT Weak #7).

    ``devices``: optional list of jax devices to spread the batch over
    (e.g. ``mesh.devices.flat``) — each device streams its own slice of
    whole histories, all dispatches in flight concurrently."""
    import jax.numpy as jnp

    K = max((s.inv_proc.shape[1] for s in segs_list), default=1)
    spec = spec_for(n_states, n_transitions, P, kernel_K(K + (K & 1)))
    if spec is None:
        return None
    B = len(segs_list)
    if B == 0:
        return []
    # slice the batch: the results buffer is VMEM-resident (2 copies:
    # carry in + out) so each dispatch is capped at MAX_STREAM_B
    # histories; with multiple devices the slices also spread across
    # them (one independent dispatch per device, all in flight at
    # once — data parallelism with zero cross-device communication)
    devs = list(devices) if devices else [None]
    plan = plan_stream_slices(B, len(devs) if devs[0] is not None
                              else 0)
    pending = []
    for start, end, dev_ix in plan:
        dev = devs[dev_ix] if devs[0] is not None else None
        pending.append(stream_dispatch(succ, segs_list[start:end],
                                       spec, n_states, n_transitions,
                                       dev))
    out = []
    try:
        for (res, starts), (start, end, _) in zip(pending, plan):
            res = np.asarray(res)   # blocks on THIS slice's device only
            note_rungs(res[:end - start])
            out.extend(merge_stream_slice(res, starts, end - start))
    except Exception:
        clear_carry_pool()          # recycled-at-dispatch carries of a
        raise                       # failed scan must not be reused
    return out


def plan_stream_slices(B: int, n_devices: int,
                       max_stream_b: Optional[int] = None):
    """Pure slice assignment for the streamed kernel (unit-testable on
    CPU — round-2 Weak #2: this logic previously ran with >1 device
    exactly nowhere). Returns ``[(start, end, device_index), ...]``
    covering ``range(B)`` in order: slices are capped at
    ``max_stream_b`` histories (VMEM results-buffer bound) and, when
    ``n_devices`` > 0, also sized to spread the whole batch across the
    devices round-robin."""
    cap = MAX_STREAM_B if max_stream_b is None else max_stream_b
    group = min(cap, -(-B // n_devices)) if n_devices > 0 else cap
    return [(i, min(i + group, B),
             ((i // group) % n_devices) if n_devices > 0 else 0)
            for i in range(0, B, group)]


def plan_shard_slices(B: int, D: int,
                      max_stream_b: Optional[int] = None):
    """Pure slice assignment for the SHARD_MAP stream path: ``B``
    (a positive multiple of ``D`` — callers pad with sentinel
    histories) splits into ``[(start, end), ...]`` slices whose width
    is always a multiple of ``D``. Within a slice, shard ``d`` owns
    the contiguous sub-range ``[start + d*g, start + (d+1)*g)`` with
    ``g = (end - start) // D`` — ONE fused dispatch covers all D
    shards per slice. Per-shard slice width is capped at
    ``max_stream_b`` (VMEM results-buffer bound)."""
    cap = MAX_STREAM_B if max_stream_b is None else max_stream_b
    if D <= 0 or B % D != 0:
        raise ValueError(f"B={B} must be a positive multiple of D={D}")
    step = min(cap, max(B // D, 1)) * D
    return [(i, min(i + step, B)) for i in range(0, B, step)]


#: segments finished on each wide rung (index r-1 for rung r) and past
#: the top rung, closed unions handed to a wider rung, and the closure
#: iterations those handovers carried (not redone), summed over every
#: verdict read back this process — the counters ride in the
#: stat/results rows, so reading them costs no extra readback
RUNG_SEGMENTS = [0] * WIDE_RUNGS
PAST_TOP = 0
RUNG_HANDOVERS = 0
ITERS_CARRIED = 0


def rung_counts(rows: np.ndarray) -> np.ndarray:
    """Per-verdict rung counters from read-back stat/results rows
    ``(n, 128)``: ``(n, WIDE_RUNGS + 1)`` — segments that finished on
    rung 1..WIDE_RUNGS, then segments past the top rung."""
    return np.asarray(rows)[:, STAT_RUNGS:STAT_PAST_TOP + 1]


def note_rungs(rows: np.ndarray, sp=None) -> np.ndarray:
    """Add read-back rows' rung counters to the process totals and
    report them (``rung_segments`` per wide rung, ``past_top``,
    ``rung_handovers``, ``iters_carried``): on span ``sp`` when given,
    else as a zero-length ``pallas.rungs`` span. Returns the summed
    rung counters (:func:`rung_counts`)."""
    global PAST_TOP, RUNG_HANDOVERS, ITERS_CARRIED
    rows = np.asarray(rows)
    tot = rung_counts(rows).sum(axis=0)
    hand = int(rows[:, STAT_HANDOVERS].sum())
    carried = int(rows[:, STAT_ITERS_CARRIED].sum())
    for r in range(WIDE_RUNGS):
        RUNG_SEGMENTS[r] += int(tot[r])
    PAST_TOP += int(tot[WIDE_RUNGS])
    RUNG_HANDOVERS += hand
    ITERS_CARRIED += carried
    args = {"rung_segments": [int(x) for x in tot[:WIDE_RUNGS]],
            "past_top": int(tot[WIDE_RUNGS]),
            "rung_handovers": hand, "iters_carried": carried}
    if sp is not None:
        sp.set(**args)
    else:
        now = _obs.monotonic()
        _obs.record("pallas.rungs", now, now, **args)
    return tot


def merge_stream_slice(res: np.ndarray, starts, n: int):
    """Pure per-slice verdict unpacking: the kernel reports fail
    segments in slice-global coordinates; callers need them history-
    local. Returns ``[(status, fail_seg_local, n_final), ...]``."""
    out = []
    for b in range(n):
        st = int(res[b, 0])
        fail_g = int(res[b, 1])
        fail_local = fail_g - int(starts[b]) if fail_g >= 0 else -1
        out.append((st, fail_local, int(res[b, 2])))
    return out


def merge_stream_shards(res: np.ndarray, starts, n: int, D: int):
    """Pure verdict unpacking for ONE sharded dispatch: ``res`` is the
    ``(D, b_pad, 128)`` results stack, ``starts[d]`` shard d's
    per-history stream offsets. Returns the slice's ``n`` verdicts in
    slice order (shard d owns the contiguous sub-range
    ``[d*g, (d+1)*g)``, matching :func:`plan_shard_slices`)."""
    g = n // D
    out = []
    for d in range(D):
        out.extend(merge_stream_slice(res[d], starts[d], g))
    return out


@functools.lru_cache(maxsize=32)
def _reset_fn(spec: SegKernelSpec, b_pad: int):
    """On-device carry re-initialization for the recycle pool: takes a
    finished dispatch's (ws, stat) device buffers DONATED, returns
    them re-filled with the initial frontier/stat constants plus a
    fresh zero results block — pure device compute, so a hot bucket's
    next dispatch ships no initial-carry bytes host-to-device."""
    import jax
    import jax.numpy as jnp

    ws_init = tuple(np.asarray(w) for w in initial_frontier(spec))
    stat_init = _init_stat()

    def carry_reset(ws, stat):
        del ws, stat            # donated: only their buffers survive
        return (tuple(jnp.asarray(w) for w in ws_init),
                jnp.asarray(stat_init),
                jnp.zeros((b_pad, LANES), jnp.int32))

    # keep_unused: the donated carries are otherwise pruned as unused
    # parameters, and then neither alias the outputs nor match the
    # program inventory's (ws, stat) signature
    return jax.jit(carry_reset, donate_argnums=(0, 1), keep_unused=True)


def _carry_recycle(key, ws, stat) -> None:
    """Return a finished dispatch's carry buffers to the pool (bounded
    per key; the results block is NOT pooled — the caller still owns
    its readback)."""
    pool = _CARRY_POOL.setdefault(key, [])
    if len(pool) < _CARRY_POOL_CAP:
        pool.append((ws, stat))


def clear_carry_pool() -> None:
    """Drop every pooled carry. Recycling happens at DISPATCH time
    (JAX is async — a device-side failure only surfaces at the
    caller's readback), so a failed dispatch's carries are already
    pooled when the error arrives; the readback sites call this on
    failure, or the poisoned buffers would re-enter every following
    same-key dispatch until restart."""
    _CARRY_POOL.clear()


def stream_dispatch(succ, segs_list, spec, n_states, n_transitions,
                    device=None):
    """Dispatch one streamed kernel call asynchronously (optionally
    pinned to ``device``); returns (res_device_array, starts). The
    caller owns the readback (``np.asarray(res)``) — the pipelined
    batch path (``checker.batch``) packs/stages the NEXT slice on the
    host while this one runs on the device.

    With carry donation on (:func:`use_carry_donation`, the default)
    the frontier/stat/results carries are donated into the scan and
    the finished (ws, stat) buffers are recycled through the carry
    pool: a hot bucket's next dispatch resets them ON DEVICE
    (:func:`_reset_fn`) instead of re-uploading initial values —
    ``CARRY_REUSES`` counts the hits."""
    import jax
    import jax.numpy as jnp

    global DISPATCHES, CARRY_REUSES
    B = len(segs_list)
    b_pad = 8                 # pow2 buckets bound kernel recompiles
    while b_pad < B:
        b_pad *= 2
    chunks, starts = pack_stream(segs_list, spec)
    table = pack_table(succ[:n_states, :n_transitions],
                       spec.table_rows_pad)

    def put(a):
        return (jax.device_put(a, device) if device is not None
                else jnp.asarray(a))

    key = (spec, b_pad, device)
    pool = _CARRY_POOL.get(key) if _DONATE else None
    if pool:
        ws_t, stat0, res0 = _reset_fn(spec, b_pad)(*pool.pop())
        CARRY_REUSES += 1
    else:
        ws_t = tuple(put(w) for w in initial_frontier(spec))
        stat0 = put(_init_stat())
        res0 = put(np.zeros((b_pad, LANES), np.int32))
    run = _scan_fn(spec, b_pad=b_pad, stream=True, donate=_DONATE)
    ws, stat, res = run(put(chunks), ws_t, stat0, res0, put(table),
                        n_transitions)
    DISPATCHES += 1
    if _DONATE:
        # ws/stat are never read back by stream callers — recycle them
        # for the next same-shape dispatch (res joins the pool only
        # implicitly, via the allocator, after the caller's readback)
        _carry_recycle(key, ws, stat)
    return res, starts


@functools.lru_cache(maxsize=32)
def _sharded_scan_fn(spec: SegKernelSpec, b_pad: int, mesh,
                     batch_axis: str):
    """shard_map-wrapped streamed scan: ONE jitted program
    (``run_sharded`` — the name the compile-surface guard keys on)
    whose per-shard body is the SAME fused kernel scan as the
    single-device path (``_scan_fn`` → ``_chunk_call``, so the Mosaic
    program is compiled once and shared — MOSAIC_BUILDS must not grow
    with D). Pure data parallelism over the mesh's batch axis: zero
    cross-shard collectives, each shard streams whole histories. The
    carry buffers (frontier words, stat row, results) are DONATED so a
    rerun/escalation resumes in place per shard without a second
    buffer allocation."""
    import jax
    from jax.sharding import PartitionSpec as P_

    run = _scan_fn(spec, b_pad=b_pad, stream=True)
    W = spec.n_words

    def body(seg, ws, stat, res, table, stride):
        out_ws, out_stat, out_res = run(
            seg[0], tuple(w[0] for w in ws), stat[0], res[0], table,
            stride)
        return (tuple(w[None] for w in out_ws), out_stat[None],
                out_res[None])

    sh = P_(batch_axis)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sh, tuple(sh for _ in range(W)), sh, sh, P_(),
                  P_()),
        out_specs=(tuple(sh for _ in range(W)), sh, sh),
        # no collectives anywhere in the kernel scan — each shard is a
        # closed computation (same reasoning as
        # linear_jax.check_device_keys_sharded)
        check_vma=False)

    def run_sharded(seg, ws, stat, res, table, stride):
        return sm(seg, ws, stat, res, table, stride)

    return jax.jit(run_sharded, donate_argnums=(1, 2, 3))


def stream_dispatch_sharded(succ, segs_list, spec, n_states,
                            n_transitions, mesh,
                            batch_axis: str = "batch"):
    """Dispatch ONE fused sharded kernel call for a slice of B
    histories split D ways over ``mesh``'s ``batch_axis`` (B % D == 0
    — callers pad with sentinel histories; see
    :func:`plan_shard_slices` for the shard sub-range layout). Every
    shard runs the per-shard single-dispatch discipline: its whole
    sub-range rides one kernel scan inside the one fused program.
    Returns ``(res, starts)`` with ``res`` the ``(D, b_pad, 128)``
    device results stack and ``starts`` the per-shard stream offsets —
    decode with :func:`merge_stream_shards`. The caller owns the
    readback (``np.asarray``), so slice i+1's host pack overlaps this
    slice's device run exactly like the single-device path."""
    global DISPATCHES
    import jax.numpy as jnp

    D = int(mesh.shape[batch_axis])
    B = len(segs_list)
    if D <= 0 or B % D != 0:
        raise ValueError(f"B={B} must be a multiple of D={D}")
    g = B // D
    b_pad = 8                 # pow2 buckets bound kernel recompiles
    while b_pad < g:
        b_pad *= 2
    packs = [pack_stream(segs_list[d * g:(d + 1) * g], spec)
             for d in range(D)]
    # histories differ in segment count, so shard chunk stacks pad to
    # a common scan length with dead segments (ok_proc = -1: no-ops)
    n_chunks = max(c.shape[0] for c, _ in packs)
    chunks = np.zeros((D, n_chunks) + packs[0][0].shape[1:], np.int32)
    chunks[:, :, :, 0] = -1
    for d, (c, _) in enumerate(packs):
        chunks[d, :c.shape[0]] = c
    starts = [s for _, s in packs]
    ws0 = initial_frontier(spec)
    ws = tuple(jnp.asarray(np.broadcast_to(w, (D,) + w.shape).copy())
               for w in ws0)
    stat = jnp.asarray(np.broadcast_to(_init_stat(),
                                       (D, 1, LANES)).copy())
    res = jnp.asarray(np.zeros((D, b_pad, LANES), np.int32))
    table = jnp.asarray(pack_table(succ[:n_states, :n_transitions],
                                   spec.table_rows_pad))
    run = _sharded_scan_fn(spec, b_pad, mesh, batch_axis)
    _, _, out_res = run(jnp.asarray(chunks), ws, stat, res, table,
                        n_transitions)
    DISPATCHES += 1
    return out_res, starts


@_obs.traced("pallas.prepare")
def _prepare(succ, segs, n_states, n_transitions, P):
    """Shared entry-point setup: spec gate, chunked segment stream,
    initial frontier + stat row (status/fail/n in lanes 0..2 — must
    match the kernel's sstat indices), packed table. Returns None when
    the shape can't run fused."""
    import jax.numpy as jnp

    spec = spec_for(n_states, n_transitions, P,
                    kernel_K(segs.inv_proc.shape[1]))
    if spec is None:
        return None
    seg_chunks = pack_segments(segs, spec)
    ws = [jnp.asarray(a) for a in initial_frontier(spec)]
    table = jnp.asarray(pack_table(succ[:n_states, :n_transitions],
                                   spec.table_rows_pad))
    return (spec, seg_chunks, ws, jnp.asarray(_init_stat()), table)


def check_device_pallas_chunked(succ: np.ndarray, segs, *,
                                n_states: int, n_transitions: int,
                                P: int, progress=None,
                                progress_interval_s: float = 5.0,
                                s_real: Optional[int] = None,
                                return_boundary: bool = False):
    """Chunk-at-a-time variant: returns to the host between kernel
    calls so ``progress(done, total, frontier_n)`` can fire (the
    reference's 5-second reporter cadence, ``linear.clj:273-297``).

    With ``return_boundary`` the result gains a 4th element
    ``(ws, done)``: the packed frontier word list at the last chunk
    boundary BEFORE the failure and the number of segments consumed up
    to it — the seed for bounded counterexample reconstruction (decode
    with :func:`decode_frontier`)."""
    import jax.numpy as jnp

    prep = _prepare(succ, segs, n_states, n_transitions, P)
    if prep is None:
        return None
    spec, seg_chunks, ws, stat, table = prep
    call = _chunk_jit(spec)
    ws = tuple(ws)
    res = jnp.zeros((8, LANES), jnp.int32)       # unused: no RESETs
    s_real = s_real if s_real is not None else segs.ok_proc.shape[0]
    t_run = _obs.monotonic()
    last = t_run
    prev_ws, done = ws, 0
    visited = 0
    # dispatches and device->host reads: one of each per chunk, one
    # read per frontier decoded (progress, boundary)
    with _obs.span("pallas.scan") as sp:
        n_calls = n_reads = 0
        for c in range(seg_chunks.shape[0]):
            off = np.array([c * spec.chunk, n_transitions], np.int32)
            ws, stat, res = call(jnp.asarray(seg_chunks[c]),
                                 jnp.asarray(off), ws, stat, res, table)
            st = np.asarray(stat)
            n_calls += 1
            n_reads += 1
            visited += int(st[0, 2]) * spec.chunk
            if int(st[0, 0]) != VALID:
                break
            # segments behind this boundary: its rows less the
            # invoke-only ones (a split segment never straddles it)
            prev_ws = ws
            done = (c + 1) * spec.chunk - int(st[0, STAT_INV])
            now = _obs.monotonic()
            if progress is not None and \
                    now - last >= progress_interval_s:
                from .linear_jax import estimated_cost

                cfgs = decode_frontier(
                    spec, [np.asarray(w) for w in ws], spec.P)
                n_reads += 1
                pend = [sum(1 for t in sl if t >= 0) for _, sl in cfgs]
                el = max(now - t_run, 1e-9)
                progress(min(done, s_real), s_real,
                         int(st[0, 2]),
                         {"visited_per_s": visited / el,
                          "segs_per_s": done / el,
                          "est_cost": estimated_cost(pend)})
                last = now
        st = np.asarray(stat)      # the last chunk's read, cached
        note_rungs(st)
        out = (int(st[0, 0]), int(st[0, 1]), int(st[0, 2]))
        if return_boundary:
            out += (([np.asarray(w) for w in prev_ws],
                     min(done, s_real)),)
            n_reads += 1
        sp.set(dispatches=n_calls, readbacks=n_reads)
    return out


def decode_frontier(spec: SegKernelSpec, ws, P: int):
    """Decode a kernel frontier (packed key word list: rung 0's row 0
    and the wide rows past ``spec.rows`` — the kernel keeps the other
    one all sentinel) into host configs ``(state, slots)`` in the
    :mod:`~.linear_host` encoding: the slot field stores LIN=0 /
    IDLE=1 / tr+2, so subtracting 2 maps straight to LIN=-2 / IDLE=-1
    / tr. Padding slots beyond ``P`` are dropped (always IDLE)."""
    keys = [np.concatenate([np.asarray(w)[0],
                            np.asarray(w)[spec.rows:].reshape(-1)])
            for w in ws]

    def field(pos, bits):
        word, sh = pos
        return (keys[word] >> sh) & ((1 << bits) - 1)

    state = field(spec.state_pos, spec.state_bits)
    slots = [field(spec.slot_pos[q], spec.slot_bits)
             for q in range(min(P, spec.P))]
    out = set()
    for lane in np.flatnonzero(keys[-1] < SENT_HI):
        out.add((int(state[lane]),
                 tuple(int(slots[q][lane]) - 2
                       for q in range(min(P, spec.P)))))
    return out


class KernelUnavailable(RuntimeError):
    """The fused kernel failed its probe on a TPU backend."""


@functools.lru_cache(maxsize=1)
def available() -> bool:
    """Whether the fused kernel serves this process.

    Off the TPU it serves only in interpret mode (CPU tests keep the
    XLA engines otherwise). On the TPU — or in interpret mode — it is
    probed once, and a probe failure RAISES :class:`KernelUnavailable`:
    quietly demoting the production path to the ~6x-slower XLA engines
    would hide a Mosaic regression behind a slow but green run."""
    from ..utils import platform

    if not _INTERPRET and not platform.on_tpu():
        return False
    from .linear_jax import make_segments
    from ..ops.packed import pack_history
    from ..ops import op as O

    h = [O.invoke(0, "w", 1), O.ok(0, "w", 1)]
    packed = pack_history(h)
    segs = make_segments(packed)
    succ = np.array([[0]], np.int32)
    try:
        r = check_device_pallas(succ, segs, n_states=1,
                                n_transitions=1, P=1)
    except Exception as e:
        raise KernelUnavailable(
            f"fused Pallas kernel probe failed: {type(e).__name__}: "
            f"{e}") from e
    if r is None or r[0] != VALID:
        raise KernelUnavailable(
            f"fused Pallas kernel probe returned {r!r}, expected VALID")
    return True
