"""The fused kernel's frontier rungs, in Pallas interpret mode on the
CPU: rung 0 (F = 128) answers exactly as the kernel did before the
wide rungs existed, a segment whose closure passes 128 hands its
closed union to the wide rung that holds it and keeps an exact
verdict, and past the top rung the segment says UNKNOWN and the
checker's ladder still answers exactly.
Verdicts and fail indices are held to the benchmark's plain reference
(``benchmark/reference.py``, ``benchmark/reference_keyed.py``), which
imports nothing of the program. Interpret compiles cost tens of
seconds per spec, so the cases are few.
"""

import os
import random
import sys

import pytest

from comdb2_tpu.checker import linear_jax as LJ
from comdb2_tpu.checker import pallas_seg as PS
from comdb2_tpu.models import model as M
from comdb2_tpu.models.memo import memo as make_memo
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.packed import pack_history

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen_keyed  # noqa: E402
import reference  # noqa: E402
import reference_keyed  # noqa: E402


@pytest.fixture()
def interpret_kernel():
    PS.use_interpret(True)
    yield
    PS.use_interpret(False)


def _plain(history):
    return [(op.process, op.type, op.f, op.value) for op in history]


def _closure_sizes(history):
    """The size of the closed configuration set at each ok completion,
    by the reference's own search: its step function and closure, as
    ``reference.check`` runs them. The reference stops expanding a
    configuration once it linearized the returning call; the kernel
    expands every configuration (``_closure_levels``), so it counts at
    least as many."""
    configs = {(None, frozenset())}
    calls, call_of, sizes = {}, {}, []
    for i, (p, t, f, v) in enumerate(history):
        if t == "invoke":
            calls[i] = (f, v)
            call_of[p] = i
            continue
        c = call_of.pop(p)
        seen, todo = set(configs), list(configs)
        while todo:
            state, done = todo.pop()
            if c in done:
                continue
            for k, (kf, kv) in calls.items():
                if k in done:
                    continue
                legal, nxt = reference._step(state, kf, kv, None)
                cfg = (nxt, done | {k})
                if legal and cfg not in seen:
                    seen.add(cfg)
                    todo.append(cfg)
        sizes.append(len(seen))
        configs = {(s, d - {c}) for s, d in seen if c in d}
        del calls[c]
    return sizes


def _closure_levels(history):
    """Per ok completion, ``(depth, levels)``: the pending calls, and
    the size of the kernel's closure after each iteration (``levels[0]``
    is the entry set; every pending call expands, up to ``depth``
    iterations or a fixed point). Stops after an ok that leaves no
    configuration. A call takes its completion's value, as the
    kernel's transitions do."""
    value, opened = {}, {}
    for i, (p, t, _, v) in enumerate(history):
        if t == "invoke":
            value[i], opened[p] = v, i
        else:
            value[opened.pop(p)] = v
    configs = {(None, frozenset())}
    calls, call_of, out = {}, {}, []
    for i, (p, t, f, v) in enumerate(history):
        if t == "invoke":
            calls[i] = (f, value[i])
            call_of[p] = i
            continue
        c = call_of.pop(p)
        closed, levels = set(configs), [len(configs)]
        for _ in range(len(calls)):
            grown = set(closed)
            for state, done in closed:
                for k, (kf, kv) in calls.items():
                    legal, nxt = reference._step(state, kf, kv, None)
                    if k not in done and legal:
                        grown.add((nxt, done | {k}))
            if len(grown) == len(closed):
                break
            closed = grown
            levels.append(len(closed))
        out.append((len(calls), levels))
        configs = {(s, d - {c}) for s, d in closed if c in d}
        del calls[c]
        if not configs:
            break
    return out


def _rung_of(n):
    """The smallest wide rung that holds ``n`` configs (past the top:
    WIDE_RUNGS + 1)."""
    return next((r for r in range(1, PS.WIDE_RUNGS + 1) if PS.F << r >= n),
                PS.WIDE_RUNGS + 1)


def _rung_counters(history):
    """The kernel's per-history counters, from the closure levels
    alone: (segments per wide rung .. past the top, handovers,
    iterations carried). A segment starts on the rung its entry set
    needs; an overflow at iteration j hands the union to the rung that
    holds it, carrying j + 1 iterations."""
    rungs = [0] * (PS.WIDE_RUNGS + 1)
    hand = carried = 0
    for _, levels in _closure_levels(history):
        r = 0 if levels[0] <= PS.F else _rung_of(levels[0])
        for j, n in enumerate(levels[1:]):
            if n > PS.F << r:
                r = _rung_of(n)
                if r > PS.WIDE_RUNGS:
                    break
                hand, carried = hand + 1, carried + j + 1
        if r:
            rungs[r - 1] += 1
        if r > PS.WIDE_RUNGS:
            break
    return rungs, hand, carried


def concurrent_writes(k: int, read_value=None):
    """``k`` processes each write a value of their own, all pending
    together, then return; then two reads of the last value (or of
    ``read_value``). At most 8 invokes share a segment, so past 8 the
    first write returns before the rest are invoked."""
    h = [O.invoke(p, "write", p) for p in range(min(k, 8))]
    if k > 8:
        h += [O.ok(0, "write", 0)]
        h += [O.invoke(p, "write", p) for p in range(8, k)]
    h += [O.ok(p, "write", p) for p in range(1 if k > 8 else 0, k)]
    last = k - 1 if read_value is None else read_value
    for i in range(2):
        h += [O.invoke(i, "read", None), O.ok(i, "read", last)]
    return h


def overflow_at_depth():
    """Five pending writes and a pending read of 0: the closure passes
    128 only on its last iteration (124 configs, then 129), so rung 0
    overflows at the depth bound and the wide rung that takes the
    union runs only the ok filter. The read's invoke carries its value,
    so the closure helpers see the kernel's transition."""
    w = [0, 3, 2, 1, 4]
    h = [O.invoke(p, "write", v) for p, v in enumerate(w)]
    h += [O.invoke(5, "read", 0)]
    h += [O.ok(p, "write", v) for p, v in enumerate(w)]
    return h + [O.ok(5, "read", 0)]


@pytest.mark.parametrize("k,invalid", [(8, False), (8, True), (12, False),
                                       (0, False)],
                         ids=["k8-wide-rung", "k8-invalid", "k12-past-top",
                              "overflow-at-depth"])
def test_concurrent_writes_cross_the_rungs(interpret_kernel, k, invalid):
    """k concurrent pending writes: by the reference's own search the
    closure passes 128 at k = 8 and passes the top rung at k = 12. The
    wide rungs give the kernel's verdict at k = 8, where the first
    segment's union is handed from rung 0 to rung 1, then to 3, then
    to 4, at its last iteration; at k = 12 the kernel says UNKNOWN past
    its top rung, and ``Linearizable().check`` on the device path still
    answers exactly, through the XLA ladder. ``overflow-at-depth``
    overflows rung 0 on its last closure iteration."""
    from comdb2_tpu.checker.checkers import Linearizable

    h = (concurrent_writes(k, read_value=99 if invalid else None) if k
         else overflow_at_depth())
    plain = _plain(h)
    peak = max(_closure_sizes(plain))
    full = max(lv[-1] for _, lv in _closure_levels(plain))
    if k:
        assert PS.F < peak <= full
        assert (peak > PS.F_TOP) == (k == 12)
        assert (full > PS.F_TOP) == (k == 12)
    else:
        depth, levels = _closure_levels(plain)[0]
        assert len(levels) == depth + 1
        assert levels[-2] <= PS.F < levels[-1] == full
    want = reference.check(plain)
    rungs0, past0 = list(PS.RUNG_SEGMENTS), PS.PAST_TOP
    hand0, carried0 = PS.RUNG_HANDOVERS, PS.ITERS_CARRIED
    got = Linearizable(backend="device").check({}, M.cas_register(), h)
    rungs = [a - b for a, b in zip(PS.RUNG_SEGMENTS, rungs0)]
    hand = PS.RUNG_HANDOVERS - hand0
    carried = PS.ITERS_CARRIED - carried0
    assert got["valid?"] is want[0]
    if not want[0]:
        assert got["op-index"] == want[1]
    assert hand >= 1 and carried >= 1
    if k == 12:
        assert PS.PAST_TOP - past0 == 1
        assert got["engines_tried"][0] == {
            "engine": "pallas-fused", "frontier_capacity": PS.F_TOP}
    else:
        assert got["engine"] == "pallas-fused"
        assert PS.PAST_TOP == past0
        # the kernel's widest closure finished on the rung that holds
        # it, and no wider
        r_full = _rung_of(full)
        assert rungs[r_full - 1] >= 1 and not any(rungs[r_full:]), \
            (full, rungs)
    if not k:
        # one handover, carrying every iteration of the segment
        assert (hand, carried) == (1, depth)


def _keyed(seed, keys, ops, twin_key):
    cfg = {"keys": keys, "procs_per_key": 10, "ops_per_key": ops,
           "values": 5, "p_info": 0.0,
           "op_mix": ["write", "cas", "cas", "read"]}
    plain, _ = gen_keyed.keyed_history(seed, 0, cfg, (twin_key, 0.5))
    from comdb2_tpu.ops.kv import tuple_
    from comdb2_tpu.ops.op import Op

    return plain, [Op(p, t, f, tuple_(*v)) for p, t, f, v in plain]


@pytest.mark.parametrize("entry", ["linearizable", "independent"])
def test_register_test_keys_match_the_reference(interpret_kernel, entry):
    """Seeded keys of the register test (10 clients, [w cas cas r], 5
    values) at a CPU size, one of them corrupted: every key's verdict
    and fail index equal the plain reference's, and some segments ran
    on a wide rung."""
    from comdb2_tpu.checker import independent
    from comdb2_tpu.checker.checkers import Linearizable

    plain, ops = _keyed(2 ** 33 + 26, keys=2, ops=200, twin_key=1)
    want = reference_keyed.check(plain)
    assert want[0][0] is True and want[1][0] is False
    rungs0 = sum(PS.RUNG_SEGMENTS)
    if entry == "independent":
        r = independent.checker(Linearizable()).check(
            {}, M.cas_register(), ops)
        got = r["results"]
    else:
        subs = independent.split(ops)
        got = {k: Linearizable().check({}, M.cas_register(), sub)
               for k, sub in subs.items()}
    for k, (valid, at) in want.items():
        assert got[k]["valid?"] is valid, (k, got[k])
        if not valid:
            assert got[k]["op-index"] == at
    assert sum(PS.RUNG_SEGMENTS) > rungs0


# Rung 0 answers as the kernel did before the wide rungs: (status,
# fail segment, n_final) of the pre-rung kernel on histories whose
# closures stay under 128, for both tiers, single-history and stream.
RUNG0_GOLDEN = {
    "p5": [(0, -1, 6), (0, -1, 4), (1, 7, 0)],
    "p10": [(0, -1, 8), (0, -1, 12), (1, 13, 0)],
}
RUNG0_TIERS = {"p5": (5, 3, 8), "p10": (10, 4, 16)}


def _rung0_histories(tier):
    from comdb2_tpu.ops.synth import mutate, register_history

    n_procs, max_pending, _ = RUNG0_TIERS[tier]
    rng = random.Random(2600 + n_procs)
    hs = [register_history(rng, n_procs=n_procs, n_events=48, values=3,
                           p_info=0.0, max_pending=max_pending)
          for _ in range(3)]
    hs += [mutate(rng, hs[0]), mutate(rng, hs[1])]
    return [hs[0], hs[1], hs[4]]        # two valid, one invalid


@pytest.mark.parametrize("tier", sorted(RUNG0_TIERS))
def test_rung0_outputs_are_the_pre_rung_kernels(interpret_kernel, tier):
    rows = RUNG0_TIERS[tier][2]
    rungs0 = list(PS.RUNG_SEGMENTS)
    counters0 = (PS.RUNG_HANDOVERS, PS.ITERS_CARRIED)
    for h, want in zip(_rung0_histories(tier), RUNG0_GOLDEN[tier]):
        packed = pack_history(h)
        mm = make_memo(M.cas_register(), packed)
        # K padded to 8: one spec per tier (pad invokes are no-ops)
        segs = LJ.make_segments(packed, k_pad=8)
        P = len(packed.process_table)
        assert PS.spec_for(16, 32, P, 8).rows == rows
        r = PS.check_device_pallas(LJ.pad_succ(mm.succ, 16, 32), segs,
                                   n_states=16, n_transitions=32, P=P)
        assert r == want
    assert PS.RUNG_SEGMENTS == rungs0          # no segment left rung 0
    assert (PS.RUNG_HANDOVERS, PS.ITERS_CARRIED) == counters0


def test_rung0_stream_outputs_are_the_pre_rung_kernels(interpret_kernel):
    from comdb2_tpu.checker.batch import _stream_segments, pack_batch

    batch = pack_batch(_rung0_histories("p5"), M.cas_register())
    segs_list, P = _stream_segments(batch)
    counters0 = (list(PS.RUNG_SEGMENTS), PS.PAST_TOP, PS.RUNG_HANDOVERS,
                 PS.ITERS_CARRIED)
    got = PS.check_device_pallas_stream(
        batch.memo.succ, segs_list, P=P, n_states=batch.memo.n_states,
        n_transitions=batch.memo.n_transitions)
    assert [tuple(g) for g in got] == RUNG0_GOLDEN["p5"]
    assert (list(PS.RUNG_SEGMENTS), PS.PAST_TOP, PS.RUNG_HANDOVERS,
            PS.ITERS_CARRIED) == counters0


def test_wide_stream_stat_rows_match_single_runs(interpret_kernel):
    """Histories that go wide, one with nine invokes in a segment (so
    invoke-only rows, as ``_wide_stream`` builds them), streamed in one
    dispatch: each history's results row (status, fail, n and every
    counter) equals its own stat row when it runs alone, and the
    counters equal those the closure levels give (``_rung_counters``):
    rungs, handovers and carried iterations, past the top included."""
    import numpy as np

    from comdb2_tpu.checker.batch import _stream_segments, pack_batch

    nine = [O.invoke(p, "write", p) for p in range(9)]
    nine += [O.ok(p, "write", p) for p in range(9)]
    nine += [O.invoke(0, "read", None), O.ok(0, "read", 8)]
    hs = [concurrent_writes(7), concurrent_writes(7, read_value=99),
          overflow_at_depth(), nine, concurrent_writes(12)]
    batch = pack_batch(hs, M.cas_register())
    segs_list, P = _stream_segments(batch, k_pad=9)
    mm = batch.memo
    spec = PS.spec_for(mm.n_states, mm.n_transitions, P, PS.K_MAX)
    res, starts = PS.stream_dispatch(mm.succ, segs_list, spec,
                                     mm.n_states, mm.n_transitions)
    res = np.asarray(res)
    lanes = slice(PS.STAT_RUNGS, PS.STAT_ITERS_CARRIED + 1)
    for b, (h, segs) in enumerate(zip(hs, segs_list)):
        before = (list(PS.RUNG_SEGMENTS), PS.PAST_TOP, PS.RUNG_HANDOVERS,
                  PS.ITERS_CARRIED)
        alone = PS.check_device_pallas(
            mm.succ, segs, n_states=mm.n_states,
            n_transitions=mm.n_transitions, P=P)
        counters = [a - c for a, c in zip(PS.RUNG_SEGMENTS, before[0])]
        counters += [PS.PAST_TOP - before[1],
                     PS.RUNG_HANDOVERS - before[2],
                     PS.ITERS_CARRIED - before[3]]
        assert PS.merge_stream_slice(res[b:b + 1], starts[b:b + 1],
                                     1)[0] == alone, b
        assert res[b, lanes].tolist() == counters, b
        rungs, hand, carried = _rung_counters(_plain(h))
        assert counters == rungs + [hand, carried], b
    assert res[:len(hs), PS.STAT_HANDOVERS].all()
    assert res[len(hs) - 1, PS.STAT_PAST_TOP] == 1


@pytest.mark.parametrize("P", [1, 2, 7, 8, 10, 15])
def test_rung_geometry(P):
    """Rungs double from 128 to the top; each rung's sort buffer holds
    its frontier and P candidate chunks in a power of two of blocks;
    the carry gains the top rung's rows."""
    assert PS.F_TOP == PS.F << PS.WIDE_RUNGS == 4096
    assert PS.WIDE_ROWS * PS.LANES == PS.F_TOP
    for r in range(1, PS.WIDE_RUNGS + 1):
        nb = PS.rung_blocks(P, r)
        assert nb & (nb - 1) == 0
        assert nb * PS.N >= (P + 1) * (PS.F << r)
        assert nb == 1 or (P + 1) * (PS.F << r) > nb * PS.N // 2
    spec = PS.spec_for(8, 14, P, 8)
    assert PS.carry_rows(spec) == spec.rows + PS.WIDE_ROWS
    stat = PS._init_stat()
    assert (PS.rung_counts(stat) == 0).all()
    assert PS.rung_counts(stat).shape == (1, PS.WIDE_RUNGS + 1)


def test_decode_frontier_reads_rung0_and_wide_rows():
    """A chunk boundary's frontier lives in rung 0's row 0 or in the
    wide rows; decode reads both (the kernel keeps the other one all
    sentinel)."""
    import numpy as np

    spec = PS.spec_for(16, 32, 3, 2)
    ws = PS.initial_frontier(spec)
    root = PS.decode_frontier(spec, ws, 3)
    assert root == {(0, (-1, -1, -1))}
    moved = [np.full_like(w, PS.SENT_LO) for w in ws]
    moved[-1][:] = PS.SENT_HI
    for w, src in zip(moved, ws):
        w[spec.rows + 5, 17] = src[0, 0]
    assert PS.decode_frontier(spec, moved, 3) == root


def _wide_stream(seed=3, S=40, K=13):
    """A SegmentStream with up to K invokes per segment, holes left in
    the invoke columns (renamed slots need not be packed left)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    ip = np.full((S, K), -1, np.int32)
    it = np.zeros((S, K), np.int32)
    for s in range(S):
        n = int(rng.integers(0, K + 1))
        cols = np.sort(rng.choice(K, n, replace=False))
        ip[s, cols] = rng.permutation(K)[:n]
        it[s, cols] = rng.integers(0, 30, n)
    ok = rng.integers(0, K, S).astype(np.int32)
    return LJ.SegmentStream(ip, it, ok, np.arange(S, dtype=np.int64) * 3,
                            rng.integers(1, 9, S).astype(np.int32))


@pytest.mark.parametrize("seed,K", [(3, 13), (4, 9), (5, 16)])
@pytest.mark.parametrize("chunk", [0, 4, 16])
def test_wide_segments_ride_invoke_only_rows(chunk, seed, K):
    """Every invoke lands, in order, on the rows of its own segment,
    at most K_MAX per row; only the last row of a segment closes it;
    with a chunk, no segment's rows straddle a chunk boundary."""
    import numpy as np

    segs = _wide_stream(seed=seed, K=K)
    okp, dep, ip, it = PS._kernel_rows(segs, PS.K_MAX, chunk)
    assert ip.shape[1] == PS.K_MAX
    closing = np.flatnonzero(okp != PS.INVOKE_ONLY)
    np.testing.assert_array_equal(okp[closing], segs.ok_proc)
    np.testing.assert_array_equal(dep[closing], segs.depth)
    assert (dep[okp == PS.INVOKE_ONLY] == 0).all()
    lo = 0
    for s, hi in enumerate(closing):
        rows = slice(lo, hi + 1)
        got = [(p, t) for p, t in zip(ip[rows].ravel(), it[rows].ravel())
               if p >= 0]
        want = [(p, t) for p, t in zip(segs.inv_proc[s], segs.inv_tr[s])
                if p >= 0]
        assert got == want
        first = hi - (-(-len(want) // PS.K_MAX) - 1 if want else 0)
        assert (okp[lo:first] == PS.INVOKE_ONLY).all()
        assert (ip[lo:first] == -1).all()            # chunk fillers
        if chunk:
            assert first // chunk == hi // chunk
        lo = hi + 1


def test_narrow_segments_pass_through_and_stream_starts_count_segments():
    import numpy as np

    segs = _wide_stream(K=6)
    out = PS._kernel_rows(segs, PS.K_MAX)
    assert out[2] is segs.inv_proc and out[0] is segs.ok_proc
    spec = PS.spec_for(16, 32, 13, 8)
    wide = _wide_stream(seed=5)
    chunks, starts = PS.pack_stream([segs, wide, segs], spec)
    flat = chunks.reshape(-1, chunks.shape[-1])
    inv_rows = np.cumsum(flat[:, 0] == PS.INVOKE_ONLY)
    # each history's start, in segment numbering, is its first row
    # less the invoke-only rows before it
    resets = np.flatnonzero(flat[:, 0] == PS.RESET)
    for b in range(3):
        first = resets[b] + 1
        assert starts[b] == first - inv_rows[first - 1]
    assert starts[1] - starts[0] == segs.ok_proc.shape[0] + 1
    assert starts[2] - starts[1] == wide.ok_proc.shape[0] + 1


@pytest.mark.parametrize("K,want", [(1, 1), (6, 6), (8, 8), (9, 8),
                                    (16, 8)])
def test_kernel_rows_carry_at_most_k_max_invokes(K, want):
    """Wider segments are still kernel-eligible: the spec's row width
    is clamped to K_MAX (spec_for itself still refuses K > 8)."""
    assert PS.kernel_K(K) == want
    assert PS.spec_for(16, 32, 10, PS.kernel_K(K)).K == want
    assert (PS.spec_for(16, 32, 10, K) is None) == (K > PS.K_MAX)
