"""Batched history packing — many independent histories, one launch.

The device analog of ``jepsen.independent``'s per-key partitioning
(``independent.clj:252-300``): N short histories (e.g. one per register
key) are checked as ONE batched/sharded device computation. This module
owns the host-side glue: interning every history's transitions into a
single shared table, memoizing the model once over that union, building
each history's per-ok segments, and choosing the engine.

The engine ladder is decided in one place (:func:`_check_batch_begin`),
from the shapes alone: the fused Pallas kernel wherever its spec
serves the batch; else the XLA engines on the shared (S, B, K) segment
tensors (:func:`_xla_begin`) — the MXU frontier engine for wide slot
counts, the key-packed engine while its 62-bit key fits, and the
vmapped segmented search as the last resort. Kernel overflows escalate
through the same XLA selection.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Union

import numpy as np

from ..models.memo import MemoizedModel, memoize_model, transitions_of
from ..models.model import Model
from ..obs import trace as _obs
from ..ops.op import FAIL, INVOKE, OK, Op
from ..ops.packed import PackedHistory, pack_history
from ..utils import next_pow2 as _next_pow2
from . import linear_jax as LJ
from . import mxu as MXU
from . import pallas_seg as PSEG

#: device->host verdict readback per history: status int32 + fail
#: index int64 + final count int32 (transfer-byte accounting — the
#: h2d side is summed from the actual staged tensors)
_D2H_BYTES_PER_LANE = 16


def _stream_nbytes(streams) -> int:
    """Host->device bytes of a list of SegmentStreams (the streamed
    kernel's per-slice payload)."""
    return sum(int(s.inv_proc.nbytes) + int(s.inv_tr.nbytes)
               + int(s.ok_proc.nbytes) + int(s.depth.nbytes)
               for s in streams)


@dataclass
class PackedBatch:
    """N histories compiled against one shared successor table."""

    packeds: List[PackedHistory]
    memo: MemoizedModel
    P: int                     # max process count (slot width)
    remaps: List[np.ndarray]   # per-history local→union trans ids

    def __len__(self) -> int:
        return len(self.packeds)


def _malformed(p: PackedHistory) -> bool:
    """True when some process invokes while an earlier invocation is
    still pending. The engines disagree on such input (relative-delta
    kernel vs absolute-set XLA), so batch paths isolate these histories
    and report them ``unknown`` — the reference wraps per-key checker
    exceptions the same way (``checker.clj:54-64`` check-safe; the
    analog raise lives in ``make_segments``).

    Vectorized via the shared per-process chain machinery
    (``ops.columnar._per_process_prev``): a non-failing invoke whose
    previous same-process event is also a non-failing invoke is a
    double-pending invocation. Cached per PackedHistory — check_batch
    and its segment helpers each consult it."""
    from ..ops.columnar import _per_process_prev

    cached = getattr(p, "_malformed_cache", None)
    if cached is not None:
        return cached
    t = np.asarray(p.type)
    inv = (t == INVOKE) & ~np.asarray(p.fails)
    sel = np.flatnonzero(inv | (t == OK) | (t == FAIL))
    if not sel.size:
        out = False
    else:
        _, inv_flag, prev_inv, _ = _per_process_prev(
            np.asarray(p.process), sel, inv)
        out = bool(np.any(inv_flag & prev_inv))
    try:
        p._malformed_cache = out
    except AttributeError:
        pass                      # slotted/frozen variants: recompute
    return out


def _empty_stream():
    """A 1-segment all-padding SegmentStream (engines yield VALID)."""
    return LJ.SegmentStream(
        np.full((1, 1), -1, np.int32), np.zeros((1, 1), np.int32),
        np.full(1, -1, np.int32), np.zeros(1, np.int64),
        np.zeros(1, np.int32))


def _segments_of(p, s_pad: int = 0, k_pad: int = 0):
    """``make_segments`` with a fast path: an exact stream cached on
    the PackedHistory (the serving layer's admission pass computes one
    per request to derive the shape bucket) is padded to the floors
    (``pad_segments``) instead of re-running the O(total-ops) host
    loop."""
    segs = getattr(p, "_segments_exact", None)
    if segs is None:
        return LJ.make_segments(p, s_pad=s_pad or None,
                                k_pad=k_pad or None)
    return LJ.pad_segments(segs, s_pad, k_pad)


@_obs.traced("batch.pack")
def pack_batch(histories: Sequence[Union[Sequence[Op], PackedHistory]],
               model: Model,
               max_states: int = 1 << 20) -> PackedBatch:
    """Pack histories for :func:`check_batch`.

    Transition ids are re-interned into one union table so all histories
    share a single memoized model; the BFS depth bound is the max
    invocation count over the batch (exact per history — a history can't
    linearize more ops than it invoked; see ``memoize_model``). Segments
    are built later, per engine, from ``packeds`` and ``remaps``.
    """
    packeds = [h if isinstance(h, PackedHistory) else pack_history(list(h))
               for h in histories]
    union: List[tuple] = []
    ids = {}
    remaps = []
    for p in packeds:
        local = []
        for t in transitions_of(p):
            if t not in ids:
                ids[t] = len(union)
                union.append(t)
            local.append(ids[t])
        remaps.append(np.asarray(local, np.int32))
    n_inv = max((int(((p.type == INVOKE) & ~p.fails).sum())
                 for p in packeds), default=0)
    mm = memoize_model(model, union, max_states=max_states, max_depth=n_inv)
    P = max((len(p.process_table) for p in packeds), default=1)
    return PackedBatch(packeds=packeds, memo=mm, P=P, remaps=remaps)


def pack_batch_masked(parent: PackedHistory, masks: Sequence,
                      memo: MemoizedModel) -> PackedBatch:
    """The shrink fast path: B sub-history candidates of ONE packed
    parent as a :class:`PackedBatch` WITHOUT re-packing or
    re-interning. Every candidate is a pair-closed row slice
    (:func:`~comdb2_tpu.ops.columnar.subset_packed`) whose id tables
    ARE the parent's, so the union transition table is the parent's
    and every remap is the identity — the O(ops·B) union pass of
    :func:`pack_batch` disappears, which is what lets a ddmin round
    test dozens of candidate sub-histories per dispatch.

    ``memo`` must be memoized over the parent's transitions with a
    depth bound >= the parent's invoke count (a candidate can't
    linearize more ops than the parent invoked, so one memo serves
    every round)."""
    from ..ops.columnar import subset_packed

    packeds = [subset_packed(parent, m) for m in masks]
    ident = np.arange(len(parent.transition_table), dtype=np.int32)
    return PackedBatch(packeds=packeds, memo=memo,
                       P=max(len(parent.process_table), 1),
                       remaps=[ident] * len(packeds))


@dataclass
class SegmentBatch:
    """Per-ok segment tensors for the XLA engines: (S, B, K) layouts."""

    inv_proc: np.ndarray   # int32[S, B, K]
    inv_tr: np.ndarray     # int32[S, B, K] — union transition ids
    ok_proc: np.ndarray    # int32[S, B]
    seg_index: np.ndarray  # int64[B, S] — segment → history index
    depth: np.ndarray      # int32[S] — max pending depth across lanes


@_obs.traced("batch.segments")
def segment_batch(batch: PackedBatch,
                  streams: Optional[list] = None,
                  s_pad: int = 0, k_pad: int = 0) -> SegmentBatch:
    """Compile each history's per-ok segments (union transition ids),
    padded to a common (S, K). Malformed histories (double-pending
    process) get an empty stream; ``check_batch`` reports them
    ``unknown``. ``streams``: per-history SegmentStreams already
    union-remapped (and possibly slot-renamed — a pure relabeling the
    XLA engines accept unchanged), e.g. from ``_stream_segments`` when
    the kernel path rejected the batch — reusing them skips a second
    O(total-ops) host segment pass. ``s_pad``/``k_pad`` are FLOORS on
    the padded segment axes: a serving layer that buckets many batches
    into a fixed (S, K) shape pins the compiled program once instead
    of recompiling per batch (the actual maxima still win when they
    exceed the floor — padding never truncates)."""
    prebuilt = streams is not None
    segss = streams if prebuilt else [
        _empty_stream() if _malformed(p) else _segments_of(p)
        for p in batch.packeds]
    S = max(_next_pow2(max((s.ok_proc.shape[0] for s in segss),
                           default=1)), s_pad)
    K = max(_next_pow2(max((s.inv_proc.shape[1] for s in segss),
                           default=1), 2), k_pad)
    ips, its, ops, idxs, deps = [], [], [], [], []
    for remap, s in zip(batch.remaps, segss):
        ds, dk = S - s.ok_proc.shape[0], K - s.inv_proc.shape[1]
        inv_proc = np.pad(s.inv_proc, ((0, ds), (0, dk)),
                          constant_values=-1)
        tr = np.pad(s.inv_tr, ((0, ds), (0, dk)))
        mask = inv_proc >= 0
        if remap.size and not prebuilt:
            tr[mask] = remap[tr[mask]]
        ips.append(inv_proc)
        its.append(tr)
        ops.append(np.pad(s.ok_proc, (0, ds), constant_values=-1))
        idxs.append(np.pad(s.seg_index, (0, ds)))
        deps.append(np.pad(s.depth, (0, ds)))
    return SegmentBatch(
        inv_proc=np.stack(ips, axis=1),    # (S, B, K)
        inv_tr=np.stack(its, axis=1),
        ok_proc=np.stack(ops, axis=1),     # (S, B)
        seg_index=np.stack(idxs, axis=0),  # (B, S)
        depth=np.max(np.stack(deps, axis=0), axis=0),   # (S,)
    )


@_obs.traced("batch.remap")
def _remapped_streams(batch: PackedBatch, indices, s_pad: int = 0,
                      k_pad: int = 0):
    """Union-remapped, slot-renamed SegmentStreams for a SUBSET of the
    batch — the unit of the pipelined dispatch (``_stream_stage``
    builds slice i+1 on the host while the device runs slice i).
    Returns ``(streams, p_eff)``; slot renaming runs the batched
    :func:`~.linear_jax.remap_slots_batch` over every history without
    an admission-time cache (``COMDB2_TPU_LEGACY_PACK=1`` routes
    through per-history ``remap_slots``)."""
    from ..ops.packed import legacy_pack_enabled

    indices = list(indices)
    out: list = [None] * len(indices)
    p_eff = 1
    need: list = []
    raw: list = []
    for j, i in enumerate(indices):
        p = batch.packeds[i]
        malformed = _malformed(p)
        s = (_empty_stream() if malformed
             else _segments_of(p, s_pad=s_pad, k_pad=k_pad))
        remap = np.asarray(batch.remaps[i], np.int32)
        if remap.size:
            inv_tr = np.where(s.inv_proc >= 0, remap[s.inv_tr],
                              0).astype(np.int32)
        else:  # no successful invokes anywhere: nothing to remap
            inv_tr = np.zeros_like(s.inv_tr, np.int32)
        cached_remap = (None if malformed
                        else getattr(p, "_remap_cache", None))
        if cached_remap is not None:
            # slot renaming depends on (inv_proc, ok_proc) only, so an
            # admission-time pass (bucket_for) is reusable verbatim —
            # just pad its exact-shape proc arrays to this stream's
            rproc, rok, pe = cached_remap
            ds = s.ok_proc.shape[0] - rok.shape[0]
            dk = s.inv_proc.shape[1] - rproc.shape[1]
            out[j] = LJ.SegmentStream(
                np.pad(rproc, ((0, ds), (0, dk)), constant_values=-1),
                inv_tr,
                np.pad(rok, (0, ds), constant_values=-1),
                s.seg_index, s.depth)
            p_eff = max(p_eff, pe)
        else:
            need.append(j)
            raw.append(LJ.SegmentStream(
                s.inv_proc, inv_tr, s.ok_proc, s.seg_index, s.depth))
    if need:
        if legacy_pack_enabled():
            renamed = [LJ.remap_slots(r) for r in raw]
            streams2 = [r[0] for r in renamed]
            pes = [r[1] for r in renamed]
        else:
            streams2, pes = LJ.remap_slots_batch(raw)
        for j, s2, pe in zip(need, streams2, pes):
            out[j] = s2
            p_eff = max(p_eff, pe)
    return out, p_eff


def _stream_segments(batch: PackedBatch, s_pad: int = 0,
                     k_pad: int = 0):
    """Per-history SegmentStreams with transition ids remapped into the
    union table (the streamed kernel shares ONE table) and process ids
    renamed to minimal reusable slots (:func:`~.linear_jax.remap_slots_batch`
    — the kernel's slot axis then scales with each history's max
    concurrent open calls, not its process count). Malformed histories
    get an empty stream; ``check_batch`` reports them ``unknown``.
    Returns ``(streams, P_eff)`` with ``P_eff`` the max effective slot
    count over the batch (the spec the ONE shared kernel compiles for).
    ``s_pad``/``k_pad`` floor each stream's padded (S, K) like
    :func:`segment_batch`'s — bucketed serving keeps the streamed
    kernel's chunk count shape-stable across batches.
    Cached on the batch (keyed by the pads): the pass is O(total ops)
    of host work, and repeat checks of the same PackedBatch (capacity
    escalation, timed bench runs) would otherwise pay it every call.
    """
    cached = getattr(batch, "_stream_seg_cache", None)
    if cached is not None and cached[0] == (s_pad, k_pad):
        return cached[1]
    out, p_eff = _remapped_streams(batch, range(len(batch.packeds)),
                                   s_pad=s_pad, k_pad=k_pad)
    batch._stream_seg_cache = ((s_pad, k_pad), (out, p_eff))
    return out, p_eff


#: histories per pipelined dispatch slice: small enough that slice
#: i+1's host pack overlaps slice i's device run on big batches, big
#: enough to amortize per-dispatch overhead (the 4096x bench packs 8
#: slices; a service-sized batch stays one slice and overlaps across
#: BUCKETS via the tick loop's double buffer instead)
PIPELINE_B = 512


def _kernel_P(p_eff: int) -> int:
    """The slot width the streamed kernel compiles for: even-bucketed
    (halves the spec space; matches ``linear._analyze_device``) while
    the fast (8,128) tier still serves it — P_eff 7 must NOT round to
    8 and fall off the ~45%-slower (16,128) tier."""
    p2 = max(p_eff, 1)
    p2 += p2 & 1
    return p2 if p2 <= PSEG.ROWS - 1 else max(p_eff, 1)


def _slice_spec(streams, sizes, p_eff_pad):
    """Kernel spec for ONE dispatch slice, derived from the renamed
    streams themselves (every allocated slot appears in the arrays, so
    max slot id + 1 IS the slice's effective P). Both the cold
    pipelined pass and the cached rerun derive specs through this one
    function — same slices, same streams, same compiled programs, so
    a warm rerun never triggers a fresh Mosaic compile."""
    pe, K = 0, 1
    for s in streams:
        K = max(K, s.inv_proc.shape[1])
        if s.inv_proc.size:
            pe = max(pe, int(s.inv_proc.max()) + 1)
        if s.ok_proc.size:
            pe = max(pe, int(s.ok_proc.max()) + 1)
    P = _kernel_P(max(pe, p_eff_pad))
    return PSEG.spec_for(sizes["n_states"], sizes["n_transitions"],
                         P, PSEG.kernel_K(K + (K & 1)))


def _slice_with_sentinels(streams, start, end, B):
    """Slice ``streams`` (the B real histories' renamed streams) for
    ``[start, end)``, appending sentinel empty streams for pad indices
    >= B. Sentinels are all-padding (every engine yields VALID on
    them) and are sliced off before any verdict/metric surfaces."""
    real = streams[start:min(end, B)]
    return real + [_empty_stream()] * ((end - start) - len(real))


def _stream_stage(batch: PackedBatch, succ, sizes, s_pad, k_pad,
                  p_eff_pad, mesh, B_pad: Optional[int] = None,
                  batch_axis: str = "batch"):
    """Stage the streamed-kernel dispatches WITHOUT blocking on
    results. On a cold batch the host segment/remap/pack pass runs
    slice-by-slice, dispatching each slice before building the next —
    JAX dispatch is async, so slice i's device run overlaps slice
    i+1's host pack (double-buffered staging; this container has one
    CPU, the overlap is host-compute vs device-compute). On a batch
    with cached streams (timed bench reruns, capacity escalation) the
    slices dispatch back-to-back from the cache.

    With a >1-device mesh the slices ride the first-class shard_map
    path: each slice is ONE fused ``stream_dispatch_sharded`` whose
    per-shard body is the production kernel scan — the host packs
    slice i+1's tensors while ALL shards run slice i. ``B_pad`` is the
    sentinel-padded batch width (D | B_pad); sentinel histories are
    excluded from verdicts and metrics by the caller's ``[:B]`` slice.

    Returns ``(pending, segs_list)``: ``pending`` is a list of
    ``(handle, start, end)`` entries for :func:`_stream_collect`
    (handle = ``(res, starts)`` single-device or
    ``(res, starts, D)`` sharded), or None when the shape can't run
    fused — ``segs_list`` is still complete then, so the XLA engines
    reuse the streams (`segment_batch(streams=...)`)."""
    B = len(batch)
    D = int(mesh.shape[batch_axis]) if mesh is not None else 0
    cap = min(PSEG.MAX_STREAM_B, PIPELINE_B)
    if D > 1:
        B_pad = B_pad if B_pad is not None else max(_next_pow2(B), D)
        plan = [(s, e, -1) for s, e in
                PSEG.plan_shard_slices(B_pad, D, max_stream_b=cap)]
        devs = [None]
        ndev = 0
    else:
        devices = (list(mesh.devices.flat) if mesh is not None
                   else None)
        ndev = len(devices) if devices else 0
        devs = devices if devices else [None]
        plan = PSEG.plan_stream_slices(B, ndev, max_stream_b=cap)
    cached = getattr(batch, "_stream_seg_cache", None)
    cached = cached[1] if cached is not None \
        and cached[0] == (s_pad, k_pad) else None

    def dispatch(streams, start, end):
        spec = _slice_spec(streams, sizes, p_eff_pad)
        if spec is None:
            return None
        with _obs.span("batch.dispatch", engine="stream",
                       start=start, end=end):
            if D > 1:
                res, starts = PSEG.stream_dispatch_sharded(
                    succ, streams, spec, sizes["n_states"],
                    sizes["n_transitions"], mesh,
                    batch_axis=batch_axis)
                return (res, starts, D)
            dix = plan_dix.get((start, end), 0)
            return PSEG.stream_dispatch(
                succ, streams, spec, sizes["n_states"],
                sizes["n_transitions"], devs[dix] if ndev else None)

    plan_dix = {(s, e): d for s, e, d in plan}
    pending: list = []
    if cached is not None:
        segs_list, _ = cached
        for start, end, _dix in plan:
            handle = dispatch(
                _slice_with_sentinels(segs_list, start, end, B),
                start, end)
            if handle is None:
                return None, segs_list
            pending.append((handle, start, end))
        return pending, segs_list
    all_streams: list = []
    p_eff_all = 1
    dead = False
    for start, end, _dix in plan:
        streams, pe = _remapped_streams(batch,
                                        range(start, min(end, B)),
                                        s_pad=s_pad, k_pad=k_pad)
        all_streams.extend(streams)
        p_eff_all = max(p_eff_all, pe)
        if dead:
            continue            # finish building the cacheable streams
        handle = dispatch(
            streams + [_empty_stream()] * ((end - start)
                                           - len(streams)),
            start, end)
        if handle is None:
            dead = True
            pending = []
            continue
        pending.append((handle, start, end))
    batch._stream_seg_cache = ((s_pad, k_pad),
                               (all_streams, p_eff_all))
    if dead:
        return None, all_streams
    return pending, all_streams


def _stream_collect(pending, B):
    """Block on the staged dispatches in order and merge the
    per-slice verdicts (each ``np.asarray`` waits on that slice's
    device only). ``B`` is the PADDED batch width when the slices were
    sharded — the caller slices sentinel verdicts off before anything
    user-visible. A readback failure clears the donated-carry pool:
    the failed scan's carries were recycled at dispatch time and must
    not seed the next same-shape dispatch. The ``batch.collect`` span
    carries the slices' rung counters (``rung_segments``,
    ``past_top``, ``rung_handovers``, ``iters_carried``), read back
    with the verdicts."""
    rs: list = [None] * B
    rows: list = []
    with _obs.span("batch.collect") as sp:
        try:
            for handle, start, end in pending:
                if len(handle) == 3:      # sharded: (res, starts, D)
                    res, starts, D = handle
                    res = np.asarray(res)
                    g = (end - start) // D
                    out = PSEG.merge_stream_shards(res, starts,
                                                   end - start, D)
                    rows.extend(res[d, :g] for d in range(D))
                else:
                    res, starts = handle
                    res = np.asarray(res)
                    out = PSEG.merge_stream_slice(res, starts,
                                                  end - start)
                    rows.append(res[:end - start])
                rs[start:end] = out
        except Exception:
            PSEG.clear_carry_pool()
            raise
        if rows:
            PSEG.note_rungs(np.concatenate(rows), sp)
    return rs


def _shard_histories(pending, B: int) -> dict:
    """Live (non-sentinel) histories each mesh device ran, read off
    where the sharded results actually live: ``{device id: count}``.
    Shard ``d`` of a slice owns rows ``[start + d*g, start + (d+1)*g)``
    (``plan_shard_slices``)."""
    per: dict = {}
    for (res, _starts, D), start, end in pending:
        g = (end - start) // D
        for sh in res.addressable_shards:
            lo = start + (sh.index[0].start or 0) * g
            n = max(min(lo + g, B) - lo, 0)
            per[sh.device.id] = per.get(sh.device.id, 0) + n
    return per


def check_batch(batch: PackedBatch, F: int = 256, mesh=None,
                batch_axis: str = "batch",
                info: Optional[dict] = None, s_pad: int = 0,
                k_pad: int = 0, n_states_pad: int = 0,
                n_transitions_pad: int = 0, p_eff_pad: int = 0):
    """Run the batched device search (see :func:`check_batch_async`);
    malformed histories (double-pending process) come back ``unknown``
    instead of poisoning the batch or diverging between engines.

    The ``*_pad`` arguments floor the padded segment axes and the
    declared memo-table sizes — a serving layer that buckets traffic
    (:mod:`comdb2_tpu.service`) pins every tensor shape and field
    width so all batches in a bucket share ONE compiled program.
    Oversizing is sound: states/transitions are ids below the real
    counts, ``pad_succ`` widens the table to match, and padding
    segments are no-ops to every engine."""
    return check_batch_async(
        batch, F=F, mesh=mesh, batch_axis=batch_axis,
        info=info, s_pad=s_pad, k_pad=k_pad,
        n_states_pad=n_states_pad,
        n_transitions_pad=n_transitions_pad, p_eff_pad=p_eff_pad)()


def check_batch_async(batch: PackedBatch, F: int = 256, mesh=None,
                      batch_axis: str = "batch",
                      info: Optional[dict] = None, s_pad: int = 0,
                      k_pad: int = 0, n_states_pad: int = 0,
                      n_transitions_pad: int = 0, p_eff_pad: int = 0):
    """Stage the batched device search and return a zero-argument
    ``finalize()`` producing ``(status[N], fail_at[N], n_final[N])``
    NumPy arrays — fail_at in history-index terms.

    Between stage and finalize the DEVICE work proceeds asynchronously
    (JAX dispatch is async; only the finalize readback blocks), so a
    caller can pack the NEXT batch's host tensors while this one runs
    — the service tick loop double-buffers exactly this way. The big-
    batch stream path additionally pipelines within one call: the host
    segments/packs dispatch slice i+1 while the device runs slice i.

    The engine follows from the shapes (module docstring): "stream"
    (the fused Pallas kernel; "stream-sharded" under a mesh), else
    "mxu", "keys" ("keys-sharded") or the last-resort "vmap".

    info: optional dict — receives {"engine": name} for the path
    actually executed (observability; tests assert on it), populated
    at stage time, and ``info["escalated"]`` when kernel overflows
    were re-run through the XLA engines.
    """
    fin = _check_batch_begin(
        batch, F=F, mesh=mesh, batch_axis=batch_axis,
        info=info, s_pad=s_pad, k_pad=k_pad,
        n_states_pad=n_states_pad,
        n_transitions_pad=n_transitions_pad, p_eff_pad=p_eff_pad)

    def finalize():
        status, fail_at, n_final = fin()
        bad = [i for i, p in enumerate(batch.packeds)
               if _malformed(p)]
        if bad:
            status = np.array(status, np.int32)
            fail_at = np.array(fail_at, np.int64)
            n_final = np.array(n_final, np.int32)
            status[bad] = LJ.UNKNOWN
            fail_at[bad] = -1
            n_final[bad] = 0
        return status, fail_at, n_final

    return finalize


def _layout(batch: PackedBatch, mesh, batch_axis: str,
            n_states_pad: int, n_transitions_pad: int):
    """``(succ, sizes, D, B_pad)``: the padded successor table, the
    declared table sizes, the mesh's shard count and the batch width
    padded for it. Declared table sizes may be floored (bucketed)
    above the real counts: ids stay below the real counts, so widening
    the fields and the padded table is a pure relabeling of the key
    layout."""
    n_states = max(batch.memo.n_states, n_states_pad)
    n_transitions = max(batch.memo.n_transitions, n_transitions_pad)
    succ = LJ.pad_succ(batch.memo.succ, _next_pow2(n_states),
                       _next_pow2(n_transitions))
    sizes = {"n_states": n_states, "n_transitions": n_transitions}
    D = int(mesh.shape[batch_axis]) if mesh is not None else 1
    if D > 1 and (D & (D - 1)):
        raise ValueError(
            f"mesh axis {batch_axis!r} must be a power of two (got "
            f"{D}): per-shard shapes are B_pad/D and must stay inside "
            "the pow2 program inventory (PROGRAMS.md mesh_D ladder)")
    # sharded engines need D | B; the pad stays pow2 so per-shard
    # shapes remain bucketed (B_pad/D is the shape each shard
    # compiles for — the shard-extended PROGRAMS.md inventory). Pad
    # lanes are SENTINEL histories, excluded from every verdict and
    # metric by the [:B] slice
    B_pad = max(_next_pow2(len(batch)), D) if D > 1 else len(batch)
    return succ, sizes, D, B_pad


def _check_batch_begin(batch: PackedBatch, F: int, mesh,
                       batch_axis: str, info: Optional[dict],
                       s_pad: int, k_pad: int, n_states_pad: int,
                       n_transitions_pad: int, p_eff_pad: int):
    """Engine selection + host packing + async device dispatch;
    returns the finalize closure (readback, fail-index decode, kernel
    overflow escalation)."""
    succ, sizes, D, B_pad = _layout(batch, mesh, batch_axis,
                                    n_states_pad, n_transitions_pad)
    B = len(batch)
    if info is not None:
        # info records the pad factor so callers can audit that dead
        # sentinel work never surfaces in per-batch totals
        info["batch"] = {"b": B, "b_pad": B_pad, "pad": B_pad - B,
                         "shards": max(D, 1)}
    xla = functools.partial(
        _xla_begin, F=F, mesh=mesh, batch_axis=batch_axis,
        s_pad=s_pad, k_pad=k_pad, n_states_pad=n_states_pad,
        n_transitions_pad=n_transitions_pad)
    # gate BEFORE the O(total-ops) segment pass so a shape that can
    # never run fused (table too big, K too wide — checked at P=1, the
    # minimum) skips the host work. P itself is NOT final here: slot
    # renaming can shrink it below the tier bound, so P-ineligible
    # shapes still try the pass when everything else fits
    if (PSEG.spec_for(sizes["n_states"], sizes["n_transitions"], 1, 8)
            is None or not PSEG.available()):
        return xla(batch, info=info)
    # the padded succ, not the raw memo table: the kernel's flat-table
    # stride is the declared n_transitions, which may be floored above
    # the real column count; p_eff_pad floors the slot count so a
    # serving layer bucketing by effective concurrency compiles one
    # kernel per bucket
    pending, segs_list = _stream_stage(
        batch, succ, sizes, s_pad, k_pad, p_eff_pad, mesh,
        B_pad=B_pad, batch_axis=batch_axis)
    if pending is None:
        # the kernel rejected the renamed shape: the XLA engines reuse
        # the streams it already built
        return xla(batch, info=info, streams=segs_list)
    if info is not None:
        # label by the route actually taken: a 1-device mesh rides
        # the plain single-device stream dispatch, not shard_map
        info["engine"] = "stream" if D <= 1 else "stream-sharded"
        # per-dispatch transfer accounting (docs/observability.md):
        # host-to-device bytes are a first-class cost — summed from
        # the actual staged tensors
        info["transfer_bytes"] = {
            "h2d": int(succ.nbytes) + _stream_nbytes(segs_list),
            "d2h": B * _D2H_BYTES_PER_LANE}

    @_obs.traced("batch.finalize")
    def finalize_stream():
        # sentinel-pad verdicts (always VALID) are sliced off HERE,
        # before escalation/metrics — a pad history can never surface
        # as a verdict, counterexample, or shrink candidate
        if D > 1 and info is not None:
            info["shard_histories"] = _shard_histories(pending, B)
        rs = _stream_collect(pending, B_pad if D > 1 else B)[:B]
        status = np.array([r[0] for r in rs], np.int32)
        fail_at = np.array([
            segs_list[b].seg_index[rs[b][1]] if rs[b][1] >= 0
            else -1 for b in range(B)], np.int64)
        n_final = np.array([r[2] for r in rs], np.int32)
        # the kernel's frontier tops out at F_TOP: histories that
        # overflowed it get the larger requested budget F through the
        # XLA engines instead of surfacing spurious UNKNOWNs. The
        # sub-batch picks its engine from ITS size — at pod-scale
        # batches the full-B budgets never fit while a handful of
        # overflowed histories easily do
        unk = escalation_indices(status, F, PSEG.F_TOP)
        if not unk.size:
            return status, fail_at, n_final
        sub = PackedBatch(packeds=[batch.packeds[i] for i in unk],
                          memo=batch.memo, P=batch.P,
                          remaps=[batch.remaps[i] for i in unk])
        sub_info: dict = {}
        st2, fa2, n2 = xla(sub, info=sub_info)()
        if info is not None:
            # the label must not claim the kernel checked them all
            info["escalated"] = {"engine": sub_info.get("engine"),
                                 "count": int(unk.size)}
        return merge_escalation(status, fail_at, n_final, unk,
                                st2, fa2, n2)

    return finalize_stream


def _xla_begin(batch: PackedBatch, *, F: int, mesh, batch_axis: str,
               info: Optional[dict], s_pad: int, k_pad: int,
               n_states_pad: int, n_transitions_pad: int,
               streams: Optional[list] = None):
    """The XLA half of the ladder, on one (S, B, K) segment batch:
    the MXU frontier engine for wide slot counts, else the key-packed
    engine (sharded under a mesh) while its key fits the per-shard
    batch, else the vmapped segmented search. The MXU engine and the
    last resort run one device; a mesh caller landing there is told
    so by ``info["mesh_dropped"]``. Returns the finalize closure."""
    succ, sizes, D, B_pad = _layout(batch, mesh, batch_axis,
                                    n_states_pad, n_transitions_pad)
    B = len(batch)
    P = _next_pow2(batch.P, 2)
    sb = segment_batch(batch, streams=streams, s_pad=s_pad, k_pad=k_pad)
    segs = (sb.inv_proc, sb.inv_tr, sb.ok_proc, sb.depth)
    if info is not None:
        info["transfer_bytes"] = {
            "h2d": int(succ.nbytes) + sum(int(a.nbytes) for a in segs),
            "d2h": B * _D2H_BYTES_PER_LANE}

    def note(name: str, single_device: bool = False) -> None:
        if info is not None:
            info["engine"] = name
            if single_device and mesh is not None:
                info["mesh_dropped"] = True

    # past the crossover (mxu.MIN_P) the MXU engine's matmul expansion
    # is P-independent while the keys per-iteration cost scales with
    # P — and most wide-P shapes don't fit the 62-bit key at all.
    # Under a mesh each device sees B_pad/D histories: the key budget
    # applies to the per-shard batch
    if MXU.serves(sizes["n_states"], sizes["n_transitions"], P):
        note("mxu", single_device=True)
        # bucket the caller's F to the engine's CAPACITIES ladder —
        # the PROGRAMS.md mxu-frontier site declares F as a closed
        # enum, and F is jit-static but invisible in the input avals,
        # so per-caller F churn would compile unseen extra programs
        # (check_batch's default F=256 rounds up to the 1024 rung)
        F_mxu = MXU.bucket_F(F)
        if info is not None:
            info["frontier_capacity"] = F_mxu
        out = MXU.check_device_mxu_batch(succ, *segs, B=B, F=F_mxu, P=P,
                                         **sizes)
    elif LJ.KeyLayout(B_pad // D, sizes["n_states"],
                      sizes["n_transitions"], P).fits:
        if mesh is not None:
            note("keys-sharded")
            out = LJ.check_device_keys_sharded(
                mesh, succ, *_pad_batch_axis(sb, B_pad - B), B=B_pad,
                F=F, P=P, batch_axis=batch_axis, **sizes)
        else:
            note("keys")
            out = LJ.check_device_keys(succ, *segs, B=B, F=F, P=P,
                                       **sizes)
    else:
        note("vmap", single_device=True)
        out = LJ.check_device_seg_batch(succ, *segs, F=F, P=P, **sizes)

    @_obs.traced("batch.finalize")
    def finalize_xla():
        status, fail_seg, n_final = (np.asarray(x)[:B] for x in out)
        fail_at = np.array([
            sb.seg_index[b, fail_seg[b]] if fail_seg[b] >= 0
            else -1 for b in range(B)], np.int64)
        return status, fail_at, n_final

    return finalize_xla


def escalation_indices(status: np.ndarray, F: int,
                       kernel_f: int) -> np.ndarray:
    """Pure: which batch indices must re-run through the XLA engines.
    Only UNKNOWN verdicts escalate, and only when the caller's
    requested frontier budget actually EXCEEDS the fused kernel's top
    rung ``kernel_f`` — re-running at a budget the kernel already
    covered could only reproduce the overflow."""
    if F <= kernel_f:
        return np.empty(0, np.int64)
    return np.flatnonzero(np.asarray(status) == LJ.UNKNOWN)


def merge_escalation(status, fail_at, n_final, idx, st2, fa2, n2):
    """Pure: fold the escalated sub-batch's verdicts back into the
    full-batch arrays at ``idx`` (unit-testable on CPU — round-2 Weak
    #2)."""
    status = np.array(status, np.int32)
    fail_at = np.array(fail_at, np.int64)
    n_final = np.array(n_final, np.int32)
    status[idx] = st2
    fail_at[idx] = fa2
    n_final[idx] = n2
    return status, fail_at, n_final


def _pad_batch_axis(sb: SegmentBatch, extra: int):
    """Widen the segment tensors' batch axis with ``extra`` dead
    histories (all segments padding) so the sharded engines' B divides
    the mesh axis; dead histories come back VALID and are sliced off."""
    if extra == 0:
        return sb.inv_proc, sb.inv_tr, sb.ok_proc, sb.depth
    ip = np.pad(sb.inv_proc, ((0, 0), (0, extra), (0, 0)),
                constant_values=-1)
    it = np.pad(sb.inv_tr, ((0, 0), (0, extra), (0, 0)))
    op_ = np.pad(sb.ok_proc, ((0, 0), (0, extra)), constant_values=-1)
    return ip, it, op_, sb.depth
