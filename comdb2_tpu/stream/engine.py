"""Device-resident carry rungs for streaming sessions.

A session's engine carry lives ON DEVICE between ``append``s — the
O(1)-per-step carried-state discipline of autoregressive-decode
caches applied to verification: each delta dispatch consumes only the
NEW segments against the resident frontier, so per-append device work
is O(delta), never O(history). Three rungs share one interface:

- **kernel** (``pallas_seg``): the fused Mosaic kernel's (ws, stat)
  word carry, chunk calls offset into the session's global segment
  stream. The kernel's frontier rungs reach 4096 configs; overflow
  past its top rung re-routes the session to the
  next rung by replaying the RETAINED renamed segments (the one
  O(history) event a session can pay, amortized over its life).
- **xla** (``stream_delta_chunk`` below — the bucketed, closed-site
  twin of ``check_device_seg2_chunk``): the (states, slots, valid, …)
  carry; capacity escalates IN PLACE via ``expand_seg_carry`` (widen
  the pre-delta carry, re-run only the delta) and the slot axis
  widens in place via ``expand_seg_carry_slots`` when the live
  history's concurrency grows. The carry is shape-portable across
  memo-table bucket growth: state ids are stable
  (:class:`~comdb2_tpu.models.memo.IncrementalMemo`) and the packed
  dedup key layout is internal to the program.
- **mxu** (``checker.mxu``): the packed-word carry for wide-P
  sessions; ``expand_carry`` escalates in place up to the 131072
  rung. The word layout bakes in (n_states, n_transitions, P), so
  table-bucket or P growth re-plans via replay.

Every delta shape rides the ``DELTA_PADS`` pow2 ladder (PROGRAMS.md
``stream-delta`` site) so the compiled-program set stays closed no
matter how a live history's appends are sized; deltas larger than the
top rung split into top-rung chunks.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..checker import linear_jax as LJ
from ..checker import mxu as MXU
from ..checker import pallas_seg as PSEG
from ..obs import trace as _obs
from ..utils import next_pow2 as _next_pow2

#: padded segments per delta dispatch — the pow2 ladder every append
#: is bucketed onto (floor 16: tiny appends share one program; top
#: 1024: larger appends split). The MXU rung floors at its declared
#: chunk ladder's minimum (64).
DELTA_PADS = (16, 64, 256, 1024)
MXU_DELTA_FLOOR = 64

#: the XLA rung's frontier ladder (same rungs as the driver's default
#: ``analysis(capacities=...)``) — in-place escalation, overflow at
#: the top is the honest UNKNOWN for P below the MXU crossover
STREAM_CAPACITIES = (256, 1024, 8192, 65536)

#: small-tier capacity of the adaptive closure (see check_device_seg2)
STREAM_FS = 32

#: stream delta dispatches this process (all rungs) — the O(delta)
#: counter tests and benches assert on. Counts launched PROGRAMS, not
#: session lanes: a megabatched advance of 8 sessions is ONE dispatch
DISPATCHES = 0

#: fused megabatch launches this process (each also counts once in
#: DISPATCHES) — the amortization counter
MEGABATCHES = 0

#: session-lane pow2 ladder of the fused megabatch entries (PROGRAMS.md
#: ``stream-delta`` session_B axis): a beat's same-shape-class lanes
#: pad up to the next rung by duplicating lane 0 (outputs discarded);
#: more than the top rung splits into top-rung launches; a single lane
#: falls back to the solo entries (no padded-lane waste)
MEGABATCH_LANES = (2, 4, 8, 16)

#: ladder ceilings (PROGRAMS.md stream-delta axes): a session whose
#: renamed concurrency or per-segment invoke burst outgrows them has
#: no declared program to run — it latches UNKNOWN (the one-shot
#: path's analog is bucket_for's host-degrade rejection; crash-heavy
#: histories pin :info slots forever and CAN get here)
STREAM_MAX_P = MXU.MAX_P
STREAM_MAX_K = 32


def bucket_delta(n_segments: int, floor: int = 0) -> int:
    """The delta_pad rung for one append's segment count (top rung
    when it exceeds the ladder — the caller then splits)."""
    for p in DELTA_PADS:
        if p >= max(n_segments, floor):
            return p
    return DELTA_PADS[-1]


@functools.partial(jax.jit, static_argnames=("F", "Fs", "P",
                                             "n_states",
                                             "n_transitions"))
def stream_delta_chunk(succ, inv_proc, inv_tr, ok_proc, depth,
                       seg_offset, carry, *, F: int, Fs: int, P: int,
                       n_states: int, n_transitions: int):
    """One delta dispatch of the XLA session rung: the adaptive
    two-tier segmented scan resumed from (and returning) a
    device-resident carry. Identical semantics to
    :func:`~comdb2_tpu.checker.linear_jax.check_device_seg2_chunk`;
    a separate jit name because THIS entry is serving surface — its
    shapes are drawn from the closed ``stream-delta`` ladder
    (PROGRAMS.md), where the driver chunk entry is an open site."""
    bits = LJ._bits_for(n_states, n_transitions, P)
    S = inv_proc.shape[0]
    segs = (inv_proc, inv_tr, ok_proc,
            seg_offset + jnp.arange(S, dtype=jnp.int32), depth)
    step = LJ._make_seg_step(succ, F, P, inv_proc.shape[1], bits,
                             Fs=LJ._seg2_tier(Fs, F))
    carry2, _ = lax.scan(step, carry, segs)
    return carry2


def _host_seg_carry(F: int, P: int):
    """Host-numpy initial carry (init_seg_carry's values): the first
    delta's jit transfers it — building it with eager jnp ops would
    compile infra programs OUTSIDE the declared surface (scatter/
    squeeze per carry shape), and the guard would rightly flag them."""
    valid = np.zeros(F, bool)
    valid[0] = True
    return (np.zeros(F, np.int32),
            np.full((F, P), LJ.IDLE, np.int32), valid,
            np.int32(1), np.int32(LJ.VALID), np.int32(-1))


def _host_expand(carry, F_new: int):
    """``expand_seg_carry`` in host numpy (escalations are rare; the
    one-time readback is cheaper than an off-inventory pad program)."""
    states, slots, valid, count, _s, _f = (np.asarray(x)
                                           for x in carry)
    pad = F_new - states.shape[0]
    if pad < 0:
        raise ValueError("carry wider than target capacity")
    return (np.pad(states, (0, pad)),
            np.pad(slots, ((0, pad), (0, 0)),
                   constant_values=LJ.IDLE),
            np.pad(valid, (0, pad)), count,
            np.int32(LJ.VALID), np.int32(-1))


class XlaCarry:
    """The XLA rung (see module docstring). ``sizes`` are the
    POW2-BUCKETED memo dims (the static shape args — raw counts here
    would compile per history, the ``unbucketed-dispatch-site``
    hazard)."""

    name = "stream-xla"

    def __init__(self, n_states: int, n_transitions: int, P2: int,
                 cap_ix: int = 0):
        self.ns = n_states
        self.nt = n_transitions
        self.P2 = P2
        self.cap_ix = cap_ix
        self.F = STREAM_CAPACITIES[cap_ix]
        self.carry = _host_seg_carry(self.F, P2)
        self._pre = self.carry          # pre-delta snapshot

    def begin_delta(self) -> None:
        self._pre = self.carry

    def dispatch(self, succ, ip, it, okp, dp, seg_offset) -> None:
        global DISPATCHES
        DISPATCHES += 1
        self.carry = stream_delta_chunk(
            succ, ip, it, okp, dp, np.int32(seg_offset), self.carry,
            F=self.F, Fs=STREAM_FS, P=self.P2, n_states=self.ns,
            n_transitions=self.nt)

    def read(self) -> Tuple[int, int, int]:
        """(status, fail_seg_global, n_final) — blocks on the device."""
        return (int(self.carry[4]), int(self.carry[5]),
                int(self.carry[3]))

    def escalate(self) -> bool:
        """Widen the PRE-delta carry to the next rung; the caller
        re-dispatches the same delta. False at the ladder top."""
        if self.cap_ix + 1 >= len(STREAM_CAPACITIES):
            return False
        self.cap_ix += 1
        self.F = STREAM_CAPACITIES[self.cap_ix]
        self.carry = _host_expand(self._pre, self.F)
        self._pre = self.carry
        return True

    def widen_slots(self, P2_new: int) -> bool:
        """Slot-axis growth IN PLACE (the rung survives concurrency
        growth without replay)."""
        self.carry = LJ.expand_seg_carry_slots(self.carry, P2_new)
        self._pre = LJ.expand_seg_carry_slots(self._pre, P2_new)
        self.P2 = P2_new
        return True

    def rebucket(self, n_states: int, n_transitions: int) -> bool:
        """Memo-table bucket growth: the carry is portable (state ids
        stable, key layout internal) — just retarget the static dims."""
        self.ns, self.nt = n_states, n_transitions
        return True

    def nbytes(self) -> int:
        st, sl, va = self.carry[0], self.carry[1], self.carry[2]
        return int(st.size * 4 + sl.size * 4 + va.size)

    def checkpoint(self) -> dict:
        """HOST-numpy snapshot of the resident carry (np.asarray is a
        readback, never a compile — the host-numpy-checkpoint rule).
        Restore's re-upload rides the next delta dispatch's jit
        transfer exactly like the initial ``_host_seg_carry``, so no
        new program joins the inventory."""
        return {"rung": "xla", "ns": self.ns, "nt": self.nt,
                "P2": self.P2, "cap_ix": self.cap_ix,
                "carry": tuple(np.asarray(x) for x in self.carry)}

    @classmethod
    def restore(cls, ck: dict) -> "XlaCarry":
        eng = cls(int(ck["ns"]), int(ck["nt"]), int(ck["P2"]),
                  cap_ix=int(ck["cap_ix"]))
        eng.carry = tuple(np.asarray(x) for x in ck["carry"])
        eng._pre = eng.carry
        return eng


class MxuCarry:
    """The MXU rung: packed-word carry, B=1 chunk form."""

    name = "stream-mxu"

    def __init__(self, n_states: int, n_transitions: int, P2: int,
                 cap_ix: int = 0):
        self.ns = n_states
        self.nt = n_transitions
        self.P2 = P2
        self.cap_ix = cap_ix
        self.F = MXU.CAPACITIES[cap_ix]
        self.carry = MXU.init_carry(1, self.F, P2,
                                    n_states=n_states,
                                    n_transitions=n_transitions)
        self._pre = self.carry

    def begin_delta(self) -> None:
        self._pre = self.carry

    def dispatch(self, succ, ip, it, okp, dp, seg_offset) -> None:
        global DISPATCHES
        DISPATCHES += 1
        self.carry = MXU.check_device_mxu_chunk(
            succ, ip, it, okp, dp, np.int32(seg_offset), self.carry,
            F=self.F, P=self.P2, n_states=self.ns,
            n_transitions=self.nt)

    def read(self) -> Tuple[int, int, int]:
        return (int(self.carry[3][0]), int(self.carry[4][0]),
                int(self.carry[2][0]))

    def escalate(self) -> bool:
        if self.cap_ix + 1 >= len(MXU.CAPACITIES):
            return False
        self.cap_ix += 1
        self.F = MXU.CAPACITIES[self.cap_ix]
        self.carry = MXU.expand_carry(self._pre, self.F)
        self._pre = self.carry
        return True

    def widen_slots(self, P2_new: int) -> bool:
        return False                    # word layout bakes P: replay

    def rebucket(self, n_states: int, n_transitions: int) -> bool:
        return False                    # PackPlan re-plans: replay

    def nbytes(self) -> int:
        words, valid = self.carry[0], self.carry[1]
        return int(sum(w.size * 4 for w in words) + valid.size)

    def checkpoint(self) -> dict:
        words, valid, n_b, status, fail = self.carry
        return {"rung": "mxu", "ns": self.ns, "nt": self.nt,
                "P2": self.P2, "cap_ix": self.cap_ix,
                "carry": (tuple(np.asarray(w) for w in words),
                          np.asarray(valid), np.asarray(n_b),
                          np.asarray(status), np.asarray(fail))}

    @classmethod
    def restore(cls, ck: dict) -> "MxuCarry":
        eng = cls(int(ck["ns"]), int(ck["nt"]), int(ck["P2"]),
                  cap_ix=int(ck["cap_ix"]))
        words, valid, n_b, status, fail = ck["carry"]
        eng.carry = (tuple(np.asarray(w) for w in words),
                     np.asarray(valid), np.asarray(n_b),
                     np.asarray(status), np.asarray(fail))
        eng._pre = eng.carry
        return eng


class KernelCarry:
    """The fused-kernel rung: (ws, stat) word carry threaded through
    per-chunk Mosaic calls at the session's global segment offset.
    The kernel's rungs go up to ``F_TOP``; any overflow or growth event
    re-routes (replay on the next rung)."""

    name = "stream-kernel"

    def __init__(self, spec, n_states: int, n_transitions: int):
        self.spec = spec
        self.ns = n_states
        self.nt = n_transitions
        self.ws = tuple(jnp.asarray(w)
                        for w in PSEG.initial_frontier(spec))
        self.stat = jnp.asarray(PSEG._init_stat())
        self._res = jnp.zeros((8, PSEG.LANES), jnp.int32)
        self._pre = (self.ws, self.stat)

    def begin_delta(self) -> None:
        self._pre = (self.ws, self.stat)

    def dispatch(self, table, chunks, seg_offset, spec=None) -> None:
        """``chunks``: (n_chunks, chunk, 2+2K) from ``pack_segments``;
        the offsets bias fail indices into session-global segment
        coordinates. ``spec`` selects a small-delta chunk rung
        (``pallas_seg.delta_spec``) — same carry geometry (rows and
        n_words are chunk-independent), smaller grid."""
        global DISPATCHES
        sp = spec or self.spec
        call = stream_kernel_chunk(sp)
        for c in range(chunks.shape[0]):
            DISPATCHES += 1
            off = np.array([seg_offset + c * sp.chunk,
                            self.nt], np.int32)
            self.ws, self.stat, self._res = call(
                jnp.asarray(chunks[c]), jnp.asarray(off), self.ws,
                self.stat, self._res, table)

    def read(self) -> Tuple[int, int, int]:
        st = np.asarray(self.stat)
        return int(st[0, 0]), int(st[0, 1]), int(st[0, 2])

    def escalate(self) -> bool:
        return False                    # past the top rung: re-route

    def widen_slots(self, P2_new: int) -> bool:
        return False                    # spec bakes P: re-route

    def rebucket(self, n_states: int, n_transitions: int) -> bool:
        return False                    # spec bakes the table: re-route

    def nbytes(self) -> int:
        return int(sum(w.size * 4 for w in self.ws)
                   + self.stat.size * 4)

    def checkpoint(self) -> dict:
        """The (ws, stat) word carry + result tile; K rides along so
        restore can re-derive the identical spec (specs are pure
        functions of (ns, nt, P2, K))."""
        return {"rung": "kernel", "ns": self.ns, "nt": self.nt,
                "K": int(self.spec.K),
                "ws": tuple(np.asarray(w) for w in self.ws),
                "stat": np.asarray(self.stat),
                "res": np.asarray(self._res)}

    @classmethod
    def restore(cls, spec, ck: dict) -> "KernelCarry":
        eng = cls(spec, int(ck["ns"]), int(ck["nt"]))
        eng.ws = tuple(np.asarray(w) for w in ck["ws"])
        eng.stat = np.asarray(ck["stat"])
        eng._res = np.asarray(ck["res"])
        eng._pre = (eng.ws, eng.stat)
        return eng


@functools.lru_cache(maxsize=16)
def stream_kernel_chunk(spec):
    """Jitted single-chunk kernel call under the session rung's OWN
    compile-log name (``_chunk_call``'s inner ``call`` is the open
    driver path; serving-surface programs must carry a declared
    name — PROGRAMS.md ``stream-delta``)."""
    call = PSEG._chunk_call(spec)

    def stream_kernel_delta(seg, off, ws, stat, res, table):
        return call(seg, off, ws, stat, res, table)

    return jax.jit(stream_kernel_delta)


@functools.partial(jax.jit, static_argnames=("F", "Fs", "P",
                                             "n_states",
                                             "n_transitions"))
def stream_delta_megabatch(succs, inv_proc, inv_tr, ok_proc, depth,
                           seg_offset, carries, *, F: int, Fs: int,
                           P: int, n_states: int, n_transitions: int):
    """B session-lanes of :func:`stream_delta_chunk` fused into ONE
    program (docs/streaming.md "Megabatched advance"): ``succs`` and
    ``carries`` are B-tuples (every session owns its memo table and
    resident carry), delta tensors are lane-major ``(B, S, K)`` /
    ``(B, S)``, ``seg_offset`` is ``(B,)``. The lane body IS the solo
    chunk scan, so vmap of its deterministic integer ops — padding
    lanes included — returns carries bit-equal to B solo dispatches
    (dead ``ok_proc=-1`` segments and latched lanes select the old
    carry inside ``_make_seg_step``). Returns a B-tuple of carries."""
    bits = LJ._bits_for(n_states, n_transitions, P)
    S, K = inv_proc.shape[1], inv_proc.shape[2]
    succ_b = jnp.stack(succs)
    carry_b = jax.tree.map(lambda *xs: jnp.stack(xs), *carries)

    def lane(succ_l, ip, it, okp, dp, off, carry):
        segs = (ip, it, okp, off + jnp.arange(S, dtype=jnp.int32), dp)
        step = LJ._make_seg_step(succ_l, F, P, K, bits,
                                 Fs=LJ._seg2_tier(Fs, F))
        carry2, _ = lax.scan(step, carry, segs)
        return carry2

    out = jax.vmap(lane)(succ_b, inv_proc, inv_tr, ok_proc, depth,
                         seg_offset, carry_b)
    return tuple(jax.tree.map(lambda x: x[i], out)
                 for i in range(len(carries)))


@functools.lru_cache(maxsize=16)
def stream_kernel_megabatch(spec, B: int):
    """B kernel-rung lanes fused into ONE jitted program: the Mosaic
    chunk program (shared via the ``_chunk_call`` cache — one build)
    is invoked once per lane INSIDE one jit, so the batch costs one
    dispatch round-trip. ``lanes`` is a B-tuple of per-lane
    ``(ws, stat, res, table)``; ``segs`` is ``(B, chunk, 2+2K)`` and
    ``offs`` ``(B, 2)`` (global segment offset + the lane's runtime
    table stride nt)."""
    call = PSEG._chunk_call(spec)

    def stream_kernel_delta_mb(segs, offs, lanes):
        out = []
        for i in range(B):
            ws, stat, res, table = lanes[i]
            out.append(call(segs[i], offs[i], ws, stat, res, table))
        return tuple(out)

    return jax.jit(stream_kernel_delta_mb)


class _Lane:
    """One session's pending delta inside a forming megabatch. The
    pack/pad closures defer array building to flush time, when the
    GROUP's pad rung (max over lanes) is known."""

    __slots__ = ("sess", "eng", "n", "k_pad", "pad_fn", "succ",
                 "seg_offset", "pack_fn", "table")

    def __init__(self, sess, eng, n, seg_offset, k_pad=0, pad_fn=None,
                 succ=None, pack_fn=None, table=None):
        self.sess = sess
        self.eng = eng
        self.n = n
        self.seg_offset = seg_offset
        self.k_pad = k_pad
        self.pad_fn = pad_fn
        self.succ = succ
        self.pack_fn = pack_fn
        self.table = table


class MegaBatch:
    """Per-beat collector fusing same-shape-class session deltas into
    one device dispatch (the tentpole of docs/streaming.md
    "Megabatched advance"). Sessions JOIN during staging
    (:meth:`~comdb2_tpu.stream.session.StreamSession.append_stage`
    with ``collector=``) and the service flushes once per beat;
    every staged finalize also flushes first, so a second append to
    one session (which forces the first's finalize) can never read a
    carry whose delta is still parked here. ``flush`` DRAINS the
    queue and is repeat-callable — later joins start a new round.

    Group keys pin everything jit-static: ``(rung, F, P2, k_pad, ns,
    nt)`` for the XLA/MXU rungs, ``("kernel", spec)`` for the fused
    kernel (nt rides per-lane in the runtime offs row). Lane counts
    pad onto the ``MEGABATCH_LANES`` pow2 ladder by duplicating lane
    0 (outputs discarded); a lone lane falls back to the solo entry.
    A group launch failure latches every joined session UNKNOWN —
    their carries never saw the delta, so letting their finalizes
    read the stale (pre-delta) carry would report a verdict for work
    that never ran."""

    def __init__(self):
        self._groups: dict = {}
        self.launches = 0        # device programs launched (all forms)
        self.fused_launches = 0  # megabatched programs (>= 2 lanes)
        self.fused_lanes = 0     # real lanes riding fused programs
        self.masked_lanes = 0    # duplicated pad lanes (discarded)
        self.solo_lanes = 0      # single-lane fallbacks
        self.lane_counts: list = []   # real lanes per launched program

    def add_delta(self, rung: str, sess, eng, n: int, k_pad: int,
                  pad_fn, succ, seg_offset: int) -> None:
        """Queue one XLA/MXU-rung delta; ``pad_fn(s_pad)`` builds the
        (ip, it, okp, dp) host arrays at the group's pad rung."""
        key = (rung, eng.F, eng.P2, k_pad, eng.ns, eng.nt)
        self._groups.setdefault(key, []).append(
            _Lane(sess, eng, n, seg_offset, k_pad=k_pad,
                  pad_fn=pad_fn, succ=succ))

    def add_kernel(self, sess, eng, n: int, pack_fn, table,
                   seg_offset: int) -> None:
        """Queue one kernel-rung delta; ``pack_fn(dspec)`` packs the
        single scalar chunk at the group's delta-chunk rung."""
        key = ("kernel", eng.spec)
        self._groups.setdefault(key, []).append(
            _Lane(sess, eng, n, seg_offset, pack_fn=pack_fn,
                  table=table))

    def add_wl(self, key: tuple, lane) -> None:
        """Queue one workload-family session delta
        (:mod:`comdb2_tpu.stream.wl`). ``key`` is the wl fuse key —
        ``("wl-bank", a_pad)`` / ``("wl-sets", e_pad)``, pinning the
        carry width the lanes must share — and ``lane`` the wl
        module's staged-lane record (it exposes ``.sess`` so the
        flush-failure latch covers wl lanes too)."""
        self._groups.setdefault(key, []).append(lane)

    def flush(self) -> None:
        while self._groups:
            groups, self._groups = self._groups, {}
            for key, lanes in groups.items():
                try:
                    self._launch_group(key, lanes)
                except Exception as e:      # noqa: BLE001 — engine
                    cause = f"engine: {type(e).__name__}: {e}"
                    for ln in lanes:
                        ln.sess._latch_unknown(cause)

    # -- launch forms --------------------------------------------------

    def _launch_group(self, key, lanes) -> None:
        if isinstance(key[0], str) and key[0].startswith("wl-"):
            from . import wl as _WL
            _WL.launch_wl_group(self, key, lanes)
            return
        top = MEGABATCH_LANES[-1]
        for i in range(0, len(lanes), top):
            chunk = lanes[i:i + top]
            if len(chunk) == 1:
                self._launch_solo(key, chunk[0])
            elif key[0] == "kernel":
                self._launch_kernel(key[1], chunk)
            else:
                self._launch_delta(key, chunk)

    def _stat(self, rung: str, b_real: int, b_pad: int, t0: float
              ) -> None:
        self.launches += 1
        self.lane_counts.append(b_real)
        if b_real == 1:
            self.solo_lanes += 1
        else:
            self.fused_launches += 1
            self.fused_lanes += b_real
            self.masked_lanes += b_pad - b_real
        _obs.record("stream.megabatch", t0, _obs.monotonic(),
                    rung=rung, lanes=b_real, masked=b_pad - b_real)

    def _launch_solo(self, key, ln) -> None:
        t0 = _obs.monotonic()
        if key[0] == "kernel":
            dspec = PSEG.delta_spec(key[1], ln.n)
            ln.eng.dispatch(ln.table, ln.pack_fn(dspec),
                            ln.seg_offset, spec=dspec)
        else:
            floor = MXU_DELTA_FLOOR if key[0] == "mxu" else 0
            s_pad = bucket_delta(ln.n, floor)
            ip, it, okp, dp = ln.pad_fn(s_pad)
            ln.eng.dispatch(ln.succ, ip, it, okp, dp, ln.seg_offset)
        ln.sess.dispatches += 1
        self._stat(key[0], 1, 1, t0)

    def _launch_kernel(self, spec, chunk) -> None:
        global DISPATCHES, MEGABATCHES
        t0 = _obs.monotonic()
        b_real = len(chunk)
        b_pad = next(b for b in MEGABATCH_LANES if b >= b_real)
        dspec = PSEG.delta_spec(spec, max(ln.n for ln in chunk))
        packs = []
        for ln in chunk:
            p = ln.pack_fn(dspec)
            if p.shape[0] != 1:         # join gate guarantees this
                raise ValueError("megabatch kernel lane spans chunks")
            packs.append(p[0])
        segs = np.stack(packs + [packs[0]] * (b_pad - b_real))
        offs = np.array(
            [[ln.seg_offset, ln.eng.nt] for ln in chunk]
            + [[chunk[0].seg_offset, chunk[0].eng.nt]]
            * (b_pad - b_real), np.int32)
        lanes_in = tuple((ln.eng.ws, ln.eng.stat, ln.eng._res,
                          ln.table) for ln in chunk)
        lanes_in += (lanes_in[0],) * (b_pad - b_real)
        DISPATCHES += 1
        MEGABATCHES += 1
        outs = stream_kernel_megabatch(dspec, b_pad)(
            jnp.asarray(segs), jnp.asarray(offs), lanes_in)
        for ln, out in zip(chunk, outs):
            ln.eng.ws, ln.eng.stat, ln.eng._res = out
            ln.sess.dispatches += 1
        self._stat("kernel", b_real, b_pad, t0)

    def _launch_delta(self, key, chunk) -> None:
        global DISPATCHES, MEGABATCHES
        t0 = _obs.monotonic()
        rung, F, P2, _k_pad, ns, nt = key
        b_real = len(chunk)
        b_pad = next(b for b in MEGABATCH_LANES if b >= b_real)
        floor = MXU_DELTA_FLOOR if rung == "mxu" else 0
        s_pad = max(bucket_delta(ln.n, floor) for ln in chunk)
        arrs = [ln.pad_fn(s_pad) for ln in chunk]
        arrs += [arrs[0]] * (b_pad - b_real)
        ip, it, okp, dp = (np.stack([a[j] for a in arrs])
                           for j in range(4))
        offs = np.array([ln.seg_offset for ln in chunk]
                        + [chunk[0].seg_offset] * (b_pad - b_real),
                        np.int32)
        succs = tuple(ln.succ for ln in chunk)
        succs += (succs[0],) * (b_pad - b_real)
        carries = tuple(ln.eng.carry for ln in chunk)
        carries += (carries[0],) * (b_pad - b_real)
        DISPATCHES += 1
        MEGABATCHES += 1
        if rung == "mxu":
            outs = MXU.check_device_mxu_megabatch(
                succs, ip, it, okp, dp, offs, carries, F=F, P=P2,
                n_states=ns, n_transitions=nt)
        else:
            outs = stream_delta_megabatch(
                succs, ip, it, okp, dp, offs, carries, F=F,
                Fs=STREAM_FS, P=P2, n_states=ns, n_transitions=nt)
        for ln, carry in zip(chunk, outs):
            ln.eng.carry = carry
            ln.sess.dispatches += 1
        self._stat(rung, b_real, b_pad, t0)


def kernel_spec(n_states: int, n_transitions: int, P2: int,
                K: int) -> Optional[object]:
    """The session's kernel spec, or None when the shape can't run
    fused (the caller then picks the MXU/XLA rung)."""
    if not PSEG.available():
        return None
    return PSEG.spec_for(n_states, n_transitions, P2, K + (K & 1))


def pick_rung(n_states: int, n_transitions: int, P2: int, K: int,
              engine: str = "auto") -> str:
    """Rung policy, mirroring the driver ladder: kernel when the
    fused spec serves the shape, MXU for wide P, XLA otherwise.
    ``engine`` forces a specific rung (tests / a session's ``rung``
    request field)."""
    if engine in ("kernel", "mxu", "xla"):
        return engine
    if P2 <= 2 * PSEG.ROWS - 1 and K <= 8 \
            and kernel_spec(n_states, n_transitions, P2, K) is not None:
        return "kernel"
    if MXU.serves(n_states, n_transitions, P2):
        return "mxu"
    return "xla"


def pad_sizes(n_states: int, n_transitions: int) -> Tuple[int, int]:
    """Pow2 memo-dim buckets (the ``stream-delta`` site's table axes —
    every dispatch must route raw counts through here)."""
    return _next_pow2(n_states), _next_pow2(n_transitions)


__all__ = ["DELTA_PADS", "DISPATCHES", "KernelCarry", "MEGABATCHES",
           "MEGABATCH_LANES", "MXU_DELTA_FLOOR", "MegaBatch",
           "MxuCarry", "STREAM_CAPACITIES", "STREAM_MAX_K",
           "STREAM_MAX_P", "XlaCarry", "bucket_delta", "kernel_spec",
           "pad_sizes", "pick_rung", "stream_delta_chunk",
           "stream_delta_megabatch", "stream_kernel_chunk",
           "stream_kernel_megabatch"]
