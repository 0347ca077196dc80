"""Slot renaming (``linear_jax.remap_slots``) — the round-5 transform
that maps process ids onto a minimal pool of reusable slots so every
engine's slot axis scales with max CONCURRENT open calls instead of
process count (the fused kernel's tier gate, round-4 Weak #4).

Renaming is a pure relabeling of a segment stream: verdicts, fail
segments, and frontier sizes must be bit-identical through any engine.
The reference's ``ArrayProcesses`` packs per-process cells densely but
never reuses them (``knossos/linear/config.clj:157-295``).
"""

import heapq
import random

import numpy as np
import pytest

import comdb2_tpu.checker.linear_jax as LJ
import comdb2_tpu.models.model as M
from comdb2_tpu.checker import counterexample as CE
from comdb2_tpu.checker import linear_host
from comdb2_tpu.checker.linear import analysis
from comdb2_tpu.models.memo import memo as make_memo
from comdb2_tpu.obs import trace
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.packed import pack_history
from comdb2_tpu.utils import next_pow2

import histgen


def _segs(h, **kw):
    return LJ.make_segments(pack_history(h), **kw)


def test_peff_tracks_concurrency_not_process_count():
    """10 processes, <=3 calls in flight -> 3 slots."""
    rng = random.Random(7)
    h = histgen.register_history(rng, n_procs=10, n_events=400,
                                 p_info=0.0, max_pending=3)
    segs = _segs(h)
    segs2, p_eff = LJ.remap_slots(segs)
    assert p_eff <= 3
    assert segs2.inv_proc.max() < p_eff
    assert segs2.ok_proc.max() < p_eff
    # untouched fields ride through
    assert segs2.seg_index is segs.seg_index
    assert segs2.depth is segs.depth
    assert segs2.inv_tr is segs.inv_tr


def test_remap_is_idempotent():
    rng = random.Random(11)
    h = histgen.register_history(rng, n_procs=8, n_events=300,
                                 p_info=0.1, max_pending=4)
    s1, p1 = LJ.remap_slots(_segs(h))
    s2, p2 = LJ.remap_slots(s1)
    assert p1 == p2
    np.testing.assert_array_equal(s1.inv_proc, s2.inv_proc)
    np.testing.assert_array_equal(s1.ok_proc, s2.ok_proc)


def test_info_invokes_pin_their_slot():
    """:info ops never complete: their slot must stay allocated (the
    process retired — reusing the slot would let a later invoke
    corrupt the still-maybe-pending op)."""
    h = [O.invoke(0, "w", 1), O.info(0, "w", 1),      # p0 crashes
         O.invoke(1, "w", 2), O.ok(1, "w", 2),
         O.invoke(2, "w", 3), O.ok(2, "w", 3)]
    segs2, p_eff = LJ.remap_slots(_segs(h))
    # p0 holds slot 0 forever; p1 gets slot 1, frees it; p2 reuses 1
    assert p_eff == 2
    ok = segs2.ok_proc[segs2.ok_proc >= 0]
    assert list(ok) == [1, 1]


def test_ok_without_open_invocation_stays_invalid():
    """A defensive path: an ok with no open call previously filtered
    on an IDLE process slot (frontier empties -> INVALID); the renamed
    stream must preserve that by mapping it to a free slot."""
    segs = LJ.SegmentStream(
        inv_proc=np.full((2, 1), -1, np.int32),
        inv_tr=np.zeros((2, 1), np.int32),
        ok_proc=np.array([0, -1], np.int32),     # ok, no invoke
        seg_index=np.zeros(2, np.int64),
        depth=np.zeros(2, np.int32))
    segs2, p_eff = LJ.remap_slots(segs)
    assert p_eff == 1
    mm = make_memo(M.register(), pack_history(
        [O.invoke(0, "w", 1), O.ok(0, "w", 1)]))
    status, fail, _ = LJ.check_device_seg2(
        LJ.pad_succ(mm.succ, 8, 8), segs2.inv_proc, segs2.inv_tr,
        segs2.ok_proc, segs2.depth, F=8, Fs=4, P=2,
        n_states=mm.n_states, n_transitions=mm.n_transitions)
    assert int(status) == LJ.INVALID
    assert int(fail) == 0


def test_double_pending_invoke_rejected():
    segs = LJ.SegmentStream(
        inv_proc=np.array([[0], [0]], np.int32),
        inv_tr=np.zeros((2, 1), np.int32),
        ok_proc=np.full(2, -1, np.int32),
        seg_index=np.zeros(2, np.int64),
        depth=np.zeros(2, np.int32))
    with pytest.raises(ValueError, match="still open"):
        LJ.remap_slots(segs)


def test_owner_maps_track_allocation():
    rng = random.Random(3)
    h = histgen.register_history(rng, n_procs=6, n_events=200,
                                 p_info=0.1, max_pending=3)
    segs = _segs(h)
    segs2, p_eff, owners = LJ.remap_slots(segs, with_maps=True)
    S, K = segs.inv_proc.shape
    alloc = {}
    for s in range(S):
        for k in range(K):
            p, sl = segs.inv_proc[s, k], segs2.inv_proc[s, k]
            if p >= 0:
                alloc[int(sl)] = int(p)
        if segs.ok_proc[s] >= 0:
            del alloc[int(segs2.ok_proc[s])]
        for q in range(p_eff):
            assert owners[s, q] == alloc.get(q, -1), (s, q)


@pytest.mark.parametrize("seed", range(40))
def test_verdict_parity_xla_engine(seed):
    """Renamed stream through the XLA seg engine == original stream ==
    host engine, across valid/invalid/info-heavy histories."""
    rng = random.Random(900 + seed)
    h = histgen.register_history(
        rng, n_procs=rng.choice([4, 8, 12]),
        n_events=rng.choice([60, 200]),
        p_info=rng.choice([0.0, 0.15]),
        max_pending=rng.choice([2, 3, 4]))
    if rng.random() < 0.5:
        h = histgen.mutate(rng, h)
    packed = pack_history(h)
    mm = make_memo(M.cas_register(), packed)
    segs = LJ.make_segments(packed, s_pad=128, k_pad=8)
    if segs.inv_proc.shape != (128, 8):
        pytest.skip("segment shape over bucket")
    segs2, p_eff = LJ.remap_slots(segs)
    # info ops pin slots forever, so the bound is max_pending plus the
    # number of crashed (info) invocations — not max_pending alone
    assert p_eff <= len(packed.process_table)
    succ = LJ.pad_succ(mm.succ, 64, 64)
    sizes = dict(n_states=mm.n_states, n_transitions=mm.n_transitions)
    P_orig = max(len(packed.process_table), 2)
    r1 = LJ.check_device_seg2(succ, segs.inv_proc, segs.inv_tr,
                              segs.ok_proc, segs.depth, F=64, Fs=8,
                              P=P_orig + (P_orig & 1), **sizes)
    r2 = LJ.check_device_seg2(succ, segs2.inv_proc, segs2.inv_tr,
                              segs2.ok_proc, segs2.depth, F=64, Fs=8,
                              P=max(p_eff + (p_eff & 1), 2), **sizes)
    assert [int(x) for x in r1] == [int(x) for x in r2]
    if int(r1[0]) != LJ.UNKNOWN:
        hr = linear_host.check(mm, packed, max_configs=1 << 18)
        assert (int(r1[0]) == LJ.VALID) == hr.valid


def test_analysis_wide_p_low_concurrency_invalid_counterexample():
    """End to end: 12 processes / concurrency 3, corrupted history.
    The driver renames slots (info reports the effective count) and
    the counterexample decodes back to ORIGINAL process ids."""
    rng = random.Random(21)
    for attempt in range(20):
        h = histgen.register_history(rng, n_procs=12, n_events=240,
                                     p_info=0.0, max_pending=3)
        h = histgen.mutate(rng, h)
        a = analysis(M.cas_register(), h, backend="device")
        if a.valid is False:
            break
    else:
        pytest.fail("no invalid mutation found")
    assert a.info.get("effective_slots", 99) <= 3
    # counterexample configs name real processes from the history
    procs = {op.process for op in h}
    for cfg in a.configs:
        assert set(cfg.get("pending", {})) <= procs
    for path in a.info.get("paths", []):
        for step in path:
            opd = step["op"]
            if isinstance(opd, dict):
                assert opd["process"] in procs


def test_segment_batch_accepts_prebuilt_renamed_streams():
    """The XLA fallback reuses the stream path's already-built
    (union-remapped, slot-renamed) streams instead of re-running the
    O(total-ops) segment pass — verdicts must match the from-scratch
    SegmentBatch through the keys engine."""
    from comdb2_tpu.checker.batch import (_stream_segments, pack_batch,
                                          segment_batch)

    rng = random.Random(17)
    hs = []
    for i in range(12):
        h = histgen.register_history(rng, n_procs=rng.randint(2, 6),
                                     n_events=rng.randint(20, 60),
                                     p_info=0.0)
        if i % 3 == 0:
            h = histgen.mutate(rng, h)
        hs.append(h)
    batch = pack_batch(hs, M.cas_register())
    streams, _ = _stream_segments(batch)
    succ = LJ.pad_succ(batch.memo.succ, 64, 64)
    sizes = dict(n_states=batch.memo.n_states,
                 n_transitions=batch.memo.n_transitions)
    P = max(batch.P + (batch.P & 1), 2)
    outs = []
    for sb in (segment_batch(batch), segment_batch(batch,
                                                   streams=streams)):
        st, fs, n = LJ.check_device_keys(
            succ, sb.inv_proc, sb.inv_tr, sb.ok_proc, sb.depth,
            B=len(batch), F=64, P=P, **sizes)
        fail_at = [int(sb.seg_index[b, int(fs[b])]) if int(fs[b]) >= 0
                   else -1 for b in range(len(batch))]
        outs.append((np.asarray(st).tolist(), fail_at,
                     np.asarray(n).tolist()))
    assert outs[0] == outs[1]


def test_pinned_slots_drive_multiword_packplan():
    """Slot renaming collapses wide-but-shallow histories, so the
    multi-word PackPlan dedup needs genuinely wide OPEN-call
    concurrency — crashed cas ops with an unreachable expected value
    pin slots forever at zero frontier cost. The device engine must
    agree with host at effective_slots ~19 (4 packed words)."""
    from comdb2_tpu.ops.synth import pinned_wide_history

    packed = pack_history(pinned_wide_history(18, with_reads=False))
    mm = make_memo(M.cas_register(), packed)
    hr = linear_host.check(mm, packed, max_configs=1 << 16)
    a = analysis(M.cas_register(), packed, backend="device")
    assert a.valid is True and hr.valid is True
    assert a.final_count == hr.final_count
    p_eff = a.info["effective_slots"]
    assert p_eff >= 18
    plan = LJ.make_pack_plan(mm.n_states, mm.n_transitions,
                             p_eff + (p_eff & 1))
    assert plan is not None and plan.n_words >= 3, plan


def test_analysis_valid_wide_p():
    rng = random.Random(5)
    h = histgen.register_history(rng, n_procs=16, n_events=300,
                                 p_info=0.0, max_pending=4)
    a = analysis(M.cas_register(), h, backend="device")
    assert a.valid is True
    assert a.info.get("effective_slots", 99) <= 5


# --- parity with the per-cell renamer --------------------------------------

def _remap_slots_oracle(segs, with_maps=False):
    """The renamer as it was before the one-pass rewrite: a Python loop
    over every (segment, k) cell, padding included, with a heap of free
    slots. Kept here as the oracle the program's renamer must match
    bit for bit."""
    S, K = segs.inv_proc.shape
    ip = segs.inv_proc.tolist()
    okl = segs.ok_proc.tolist()
    out_ip = [row[:] for row in ip]
    out_ok = list(okl)
    slot_of: dict = {}
    free: list = []
    n_slots = 0
    maps = [] if with_maps else None
    owners: list = []
    for s in range(S):
        row = ip[s]
        orow = out_ip[s]
        for k in range(K):
            p = row[k]
            if p < 0:
                continue
            if p in slot_of:
                raise ValueError(
                    f"process {p} invokes in segment {s} while an "
                    "earlier invocation is still open")
            if free:
                sl = heapq.heappop(free)
            else:
                sl = n_slots
                n_slots += 1
                owners.append(-1)
            slot_of[p] = sl
            owners[sl] = p
            orow[k] = sl
        o = okl[s]
        if o >= 0:
            sl = slot_of.pop(o, None)
            if sl is None:
                if free:
                    out_ok[s] = free[0]
                else:
                    out_ok[s] = n_slots
                    n_slots += 1
                    owners.append(-1)
                    heapq.heappush(free, out_ok[s])
            else:
                out_ok[s] = sl
                owners[sl] = -1
                heapq.heappush(free, sl)
        if with_maps:
            maps.append(owners[:])
    P_eff = n_slots
    segs2 = LJ.SegmentStream(
        np.asarray(out_ip, np.int32).reshape(S, K),
        segs.inv_tr, np.asarray(out_ok, np.int32),
        segs.seg_index, segs.depth)
    if with_maps:
        pos = np.full((S, max(P_eff, 1)), -1, np.int32)
        for s, row in enumerate(maps):
            if row:
                pos[s, :len(row)] = row
        return segs2, P_eff, pos
    return segs2, P_eff


def _random_stream(rng, n_procs=6, S=400, K=4):
    """A segment stream made directly, not from a history: invokes at
    random k positions (holes between them), and one ok in ten from a
    process with no open call (the unmatched-ok branch, with and
    without a free slot), some segments with no ok at all."""
    inv_proc = np.full((S, K), -1, np.int32)
    ok_proc = np.full(S, -1, np.int32)
    open_ = set()
    for s in range(S):
        idle = [p for p in range(n_procs) if p not in open_]
        rng.shuffle(idle)
        cols = sorted(rng.sample(range(K), rng.randint(0, K)))
        for k, p in zip(cols, idle):
            inv_proc[s, k] = p
            open_.add(p)
        r = rng.random()
        if r < 0.1:
            ok_proc[s] = rng.choice([p for p in range(n_procs + 3)
                                     if p not in open_])
        elif r < 0.9 and open_:
            p = rng.choice(sorted(open_))
            ok_proc[s] = p
            open_.discard(p)
    return LJ.SegmentStream(inv_proc, np.zeros((S, K), np.int32), ok_proc,
                            np.arange(S, dtype=np.int64),
                            np.zeros(S, np.int32))


def _family(name):
    """(stream, minimum P_eff the case must reach)."""
    rng = random.Random(f"remap-{name}")
    if name == "unmatched-ok":
        return _random_stream(rng), 1
    kw = {"register": dict(n_procs=5, n_events=4000, values=5,
                           p_info=0.0),
          "cas-p10": dict(n_procs=10, n_events=3000, p_info=0.05),
          "crash-heavy": dict(n_procs=6, n_events=2000, p_info=0.3),
          "wide": dict(n_procs=12, n_events=3000, p_info=0.4)}[name]
    h = histgen.register_history(rng, **kw)
    return _segs(h), {"register": 1, "cas-p10": 1, "crash-heavy": 2,
                      "wide": 65}[name]


def _assert_same_stream(a, b):
    for name, x, y in zip(LJ.SegmentStream._fields, a, b):
        assert x.dtype == y.dtype and x.shape == y.shape, name
        np.testing.assert_array_equal(x, y, err_msg=name)


@pytest.mark.parametrize("pad", ["exact", "bucket", "floor"])
@pytest.mark.parametrize("family", ["register", "cas-p10", "crash-heavy",
                                    "wide", "unmatched-ok"])
def test_remap_matches_per_cell_oracle(family, pad):
    """Every array, P_eff and the owner map equal the per-cell renamer
    bit for bit: on the cell's register shape, a 10-process cas
    history, crash-heavy histories whose :info calls pin slots for
    good, a case past 64 slots (the free-slot bitmask past one word),
    and streams with unmatched oks — each exact, padded to the device
    path's shape bucket, and padded to a larger floor."""
    segs, min_peff = _family(family)
    S, K = segs.inv_proc.shape
    if pad == "bucket":
        segs = LJ.pad_segments(segs, next_pow2(S, 64), next_pow2(K, 2))
    elif pad == "floor":
        segs = LJ.pad_segments(segs, 2 * S + 3, K + 5)
    got, p_eff, owners = LJ.remap_slots(segs, with_maps=True)
    want, p_want, owners_want = _remap_slots_oracle(segs, with_maps=True)
    assert p_eff == p_want >= min_peff
    _assert_same_stream(got, want)
    assert owners.dtype == owners_want.dtype
    np.testing.assert_array_equal(owners, owners_want)
    got2, p_eff2 = LJ.remap_slots(segs)
    assert p_eff2 == p_want
    _assert_same_stream(got2, want)


def test_remap_double_invoke_names_the_same_segment():
    segs, _ = _family("unmatched-ok")
    inv = segs.inv_proc.copy()
    s, k = np.argwhere(inv >= 0)[len(np.argwhere(inv >= 0)) // 2]
    # the process invoked at (s, k) invokes again, still open, in the
    # next segment that has a free column
    p = int(inv[s, k])
    ok = segs.ok_proc.copy()
    ok[s:] = np.where(ok[s:] == p, -1, ok[s:])
    t = next(t for t in range(s + 1, inv.shape[0]) if (inv[t] < 0).any()
             and p not in inv[t])
    inv[t, int(np.flatnonzero(inv[t] < 0)[0])] = p
    bad = segs._replace(inv_proc=inv, ok_proc=ok)
    with pytest.raises(ValueError) as want:
        _remap_slots_oracle(bad)
    with pytest.raises(ValueError, match="still open") as got:
        LJ.remap_slots(bad)
    assert str(got.value) == str(want.value)


@pytest.mark.parametrize("family", ["register", "crash-heavy"])
def test_pad_segments_equals_a_padded_build(family):
    rng = random.Random(f"pad-{family}")
    h = histgen.register_history(rng, n_procs=6, n_events=1500,
                                 p_info=0.3 if family == "crash-heavy"
                                 else 0.0)
    packed = pack_history(h)
    segs = LJ.make_segments(packed)
    S, K = segs.inv_proc.shape
    for s_pad, k_pad in ((next_pow2(S, 64), next_pow2(K, 2)), (S, K),
                         (S + 1, K)):
        _assert_same_stream(
            LJ.pad_segments(segs, s_pad, k_pad),
            LJ.make_segments(packed, s_pad=s_pad, k_pad=k_pad))


# --- the analysis and the twin's reconstruction ---------------------------

def _twin(seed):
    """A 5-process register history whose first ok read from half the
    history on returns a value never written."""
    h = list(histgen.register_history(random.Random(seed), n_procs=5,
                                      n_events=400, values=5,
                                      p_info=0.0))
    i = next(i for i, op in enumerate(h) if i >= len(h) // 2
             and op.type == "ok" and op.f == "read")
    h[i] = O.Op(h[i].process, "ok", "read", 99)
    return h


def _count_builds(monkeypatch):
    calls = []
    build = LJ.make_segments

    def counted(*a, **kw):
        calls.append(kw)
        return build(*a, **kw)

    monkeypatch.setattr(LJ, "make_segments", counted)
    return calls


def _spans_of(fn):
    trace.clear()
    trace.enable()
    try:
        out = fn()
        return out, [s for s in trace.spans()
                     if s.name == "linear.segments"]
    finally:
        trace.disable()
        trace.clear()


def test_device_analysis_segment_span_counts_its_builds(monkeypatch):
    """The `linear.segments` span's `builds` arg is the number of
    `make_segments` calls: one exact build to learn the shape bucket,
    one padded to it."""
    calls = _count_builds(monkeypatch)
    h = histgen.register_history(random.Random(1), n_procs=5,
                                 n_events=400, values=5, p_info=0.0)
    a, spans = _spans_of(
        lambda: analysis(M.cas_register(), h, backend="device"))
    assert a.valid is True
    assert len(calls) == 2 and calls[0] == {}
    assert set(calls[1]) == {"s_pad", "k_pad"}
    assert [s.args["builds"] for s in spans] == [2]


def test_counterexample_matches_the_per_cell_renamer(monkeypatch):
    """The twin's verdict, the dying op, the paths and the configs are
    those the per-cell renamer gives on the same history (op 211 for
    this seed), through the analysis and the reconstruction alone."""
    h = _twin(1)
    packed = pack_history(h)
    mm = make_memo(M.cas_register(), packed)
    monkeypatch.setattr(LJ, "remap_slots", _remap_slots_oracle)
    want_a = analysis(M.cas_register(), h, backend="device")
    want_ce = CE.reconstruct(mm, packed)
    monkeypatch.undo()

    calls = _count_builds(monkeypatch)
    a, spans = _spans_of(
        lambda: analysis(M.cas_register(), h, backend="device"))
    assert len(calls) == 4
    assert [s.args["builds"] for s in spans] == [2, 2]
    assert a.valid is False is want_a.valid
    assert a.op_index == want_a.op_index == 211
    assert a.info["paths"] == want_a.info["paths"]
    assert a.configs == want_a.configs

    calls.clear()
    ce, spans = _spans_of(lambda: CE.reconstruct(mm, packed))
    assert len(calls) == 2
    assert [s.args["builds"] for s in spans] == [2]
    assert ce.op_index == want_ce.op_index == 211
    assert ce.paths == want_ce.paths and len(ce.paths) == 1
    assert ce.configs == want_ce.configs
