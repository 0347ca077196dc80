"""Device (JAX) frontier search vs. the host reference engine."""

import random

import numpy as np
import pytest

from comdb2_tpu.checker import analysis
from comdb2_tpu.checker import linear_host, linear_jax as LJ
from comdb2_tpu.checker.linear import _next_pow2
from comdb2_tpu.models.memo import memo as make_memo
from comdb2_tpu.models import model as M
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.packed import pack_history

import histgen
from engine_oracles import last_resort_nt


def device_check(model, history, F=64):
    packed = pack_history(history)
    mm = make_memo(model, packed)
    P = max(1, len(packed.process_table))
    stream = LJ.make_stream(packed)
    status, fail_at, n = LJ.check_device(
        LJ.pad_succ(mm.succ), *stream, F=F, P=P)
    return int(status), int(fail_at), int(n)


def test_device_simple_valid():
    h = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
         O.invoke(0, "read", None), O.ok(0, "read", 1)]
    status, _, n = device_check(M.register(), h)
    assert status == LJ.VALID and n >= 1


def test_device_simple_invalid():
    h = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
         O.invoke(0, "read", None), O.ok(0, "read", 2)]
    status, fail_at, _ = device_check(M.register(), h)
    assert status == LJ.INVALID
    assert fail_at == 3


def test_device_overflow_is_unknown():
    # many concurrent crashed writes of distinct values -> frontier blowup
    h = []
    for i in range(12):
        h.append(O.invoke(i, "write", i))
        h.append(O.info(i, "write", i))
    h += [O.invoke(100, "read", None), O.ok(100, "read", 5)]
    status, _, _ = device_check(M.register(), h, F=4)
    assert status == LJ.UNKNOWN


@pytest.mark.parametrize("seed", range(40))
def test_device_matches_host_random(seed):
    rng = random.Random(77_000 + seed)
    h = histgen.register_history(rng, n_procs=rng.randint(2, 4),
                                 n_events=rng.randint(4, 16),
                                 p_info=0.1)
    if rng.random() < 0.6:
        h = histgen.mutate(rng, h)
    model = M.cas_register()
    packed = pack_history(h)
    mm = make_memo(model, packed)
    hr = linear_host.check(mm, packed)
    status, fail_at, _ = device_check(model, h, F=256)
    assert status in (LJ.VALID, LJ.INVALID)
    assert (status == LJ.VALID) == hr.valid, f"host={hr.valid}"
    if status == LJ.INVALID:
        assert fail_at == hr.op_index


@pytest.mark.parametrize("packed_path", [False, True])
def test_seg2_adaptive_matches_host_fuzz(packed_path):
    """The two-tier engine (small closure + per-segment escalation) must
    agree with the host reference on verdicts AND fail indices. Shapes
    are bucketed so the whole fuzz shares a few compiled programs.

    packed_path=True sizes the state space so the two-word packed dedup
    (incl. the returned-first bit at hi bit 29) is what runs; False
    forces the full-lexsort fallback (P=8 slots never fit the budget)."""
    if packed_path:
        P, sizes = 4, dict(n_states=16, n_transitions=16)
        assert LJ.pack_bits(16, 16, P)[2]     # the budget must fit
    else:
        P, sizes = 8, dict(n_states=64, n_transitions=64)
        assert not LJ.pack_bits(64, 64, P)[2]
    hits = 0
    for seed in range(88_000, 88_120):
        rng = random.Random(seed)
        h = histgen.register_history(
            rng, n_procs=rng.randint(2, 3 if packed_path else 5),
            n_events=rng.randint(6, 40),
            p_info=0.05 if packed_path else 0.15,
            values=2 if packed_path else 5)
        if rng.random() < 0.5:
            h = histgen.mutate(rng, h)
        packed = pack_history(h)
        mm = make_memo(M.cas_register(), packed)
        if (len(packed.process_table) > P
                or mm.n_states > sizes["n_states"]
                or mm.n_transitions > sizes["n_transitions"]):
            continue
        segs = LJ.make_segments(packed, s_pad=32, k_pad=8)
        if segs.inv_proc.shape != (32, 8):
            continue
        hr = linear_host.check(mm, packed, max_configs=1 << 18)
        # sizes are bucketed to keep one jit signature; padding ids are
        # unreachable so semantics are unchanged
        status, fa, _ = LJ.check_device_seg2(
            LJ.pad_succ(mm.succ, sizes["n_states"],
                        sizes["n_transitions"]),
            segs.inv_proc, segs.inv_tr,
            segs.ok_proc, segs.depth, F=64, Fs=8, P=P, **sizes)
        if int(status) == LJ.UNKNOWN:
            continue            # F=64 overflow: sound, just imprecise
        assert (int(status) == LJ.VALID) == hr.valid, f"seed={seed}"
        if int(status) == LJ.INVALID:
            assert int(segs.seg_index[int(fa)]) == hr.op_index, \
                f"seed={seed}"
        hits += 1
    assert hits > 60      # the fuzz must mostly exercise the engine


def test_analysis_device_backend():
    rng = random.Random(5)
    h = histgen.register_history(rng, n_procs=3, n_events=40)
    a = analysis(M.cas_register(), h, backend="device")
    assert a.valid is True
    h2 = histgen.mutate(random.Random(6), h)
    from comdb2_tpu.checker.brute import brute_valid
    a2 = analysis(M.cas_register(), h2, backend="device")
    assert a2.valid == brute_valid(M.cas_register(), h2)
    if a2.valid is False:
        assert a2.op is not None


def test_analysis_auto_small_uses_host():
    h = [O.invoke(0, "write", 1), O.ok(0, "write", 1)]
    a = analysis(M.register(), h)
    assert a.valid is True
    assert a.info["backend"] == "host"


# --- batched ----------------------------------------------------------------

def test_device_batch():
    from comdb2_tpu.checker.batch import pack_batch, check_batch

    model = M.cas_register()
    histories, want = [], []
    for seed in range(16):
        rng = random.Random(31_000 + seed)
        h = histgen.register_history(rng, n_procs=3,
                                     n_events=rng.randint(6, 14))
        if seed % 2:
            h = histgen.mutate(rng, h)
        histories.append(h)
        packed = pack_history(h)
        mm = make_memo(model, packed)
        want.append(linear_host.check(mm, packed).valid)
    batch = pack_batch(histories, model)
    status, fail_at, n = check_batch(batch, F=128)
    got = [s == LJ.VALID for s in status]
    assert got == want


def test_last_resort_matches_host_fuzz(monkeypatch):
    """The batch path's last resort (the segmented search vmapped over
    lanes) must agree with the host engine on random mixed batches.
    Five or more processes at a 64-transition table are past the keys
    engine's 62-bit key, so check_batch itself routes them there; info
    ops can widen a process table past the MXU crossover, so that
    engine is switched off."""
    from comdb2_tpu.checker.batch import pack_batch, check_batch

    monkeypatch.setenv("COMDB2_TPU_MXU", "0")

    model = M.cas_register()
    for round_ in range(4):
        histories, want = [], []
        for seed in range(12):
            rng = random.Random(77_000 + round_ * 100 + seed)
            h = histgen.register_history(
                rng, n_procs=rng.randint(5, 8),
                n_events=rng.randint(5, 24),
                p_info=0.1 if seed % 3 == 0 else 0.0)
            if seed % 2:
                h = histgen.mutate(rng, h)
            histories.append(h)
            packed = pack_history(h)
            mm = make_memo(model, packed)
            want.append(linear_host.check(mm, packed).valid)
        batch = pack_batch(histories, model)
        info = {}
        status, fail_at, n = check_batch(
            batch, F=1024, n_transitions_pad=last_resort_nt(batch),
            info=info)
        assert info["engine"] == "vmap", info
        got = [s == LJ.VALID for s in status]
        assert got == want, (round_, got, want)


def test_flat_engines_overflow_unknown():
    """A batch lane whose frontier exceeds F must come back UNKNOWN,
    not a wrong definite verdict — in the keys engine and in the last
    resort."""
    from comdb2_tpu.checker.batch import pack_batch, check_batch

    model = M.cas_register()
    rng = random.Random(99)
    # concurrent pending ops -> frontier larger than a tiny F
    # (p_info=0 keeps the process table narrow so the key budget fits)
    wide = histgen.register_history(rng, n_procs=4, n_events=60,
                                    p_info=0.0)
    small = histgen.register_history(random.Random(1), n_procs=2,
                                     n_events=8, p_info=0.0)
    batch = pack_batch([wide, small], model)
    for engine, nt in (("keys", 0), ("vmap", last_resort_nt(batch))):
        info = {}
        status, _, _ = check_batch(batch, F=2, n_transitions_pad=nt,
                                   info=info)
        assert info["engine"] == engine, info
        assert status[0] == LJ.UNKNOWN, (engine, status)
        assert status[1] in (LJ.VALID, LJ.UNKNOWN)


def test_pack_bits_rejects_fragmented_budgets():
    """fits must simulate the greedy per-word split: totals that fit 61
    bits can still overflow one word once fields can't straddle."""
    sb, tb, fits = LJ.pack_bits(1 << 20, (1 << 20) - 2, 2)
    assert not fits                     # 20+20+20: hi word gets 40 bits
    assert LJ.pack_bits(8, 30, 8)[2]    # 3 + 8*5 splits fine
    # KeyLayout (batch field included) says no to 18+20+20+20 bits
    assert not LJ.KeyLayout(2, 1 << 18, (1 << 20) - 2, 2).fits


def test_pack_words_injective_when_fits():
    """Whenever pack_bits accepts a shape, distinct configs must get
    distinct fingerprints."""
    import jax.numpy as jnp

    rng = random.Random(4)
    for n_states, n_tr, P in ((6, 26, 4), (1 << 14, 14, 2), (4, 1 << 13, 2)):
        sb, tb, fits = LJ.pack_bits(n_states, n_tr, P)
        if not fits:
            continue
        rows = set()
        configs = []
        for _ in range(200):
            st = rng.randrange(n_states)
            sl = tuple(rng.randrange(-2, n_tr) for _ in range(P))
            if (st, sl) not in rows:
                rows.add((st, sl))
                configs.append((st, sl))
        states = jnp.asarray([c[0] for c in configs], jnp.int32)
        slots = jnp.asarray([c[1] for c in configs], jnp.int32)
        plan = LJ.make_pack_plan(n_states, n_tr, P)
        assert plan is not None and plan.n_words <= 2
        words = LJ._pack_plan_words(states, slots, plan)
        pairs = set(zip(*(np.asarray(w).tolist() for w in words)))
        assert len(pairs) == len(configs), (n_states, n_tr, P)


def test_malformed_history_isolated_in_batch():
    """A double-pending history (bypassing history.complete) must come
    back `unknown` without poisoning the rest of the batch — the
    check-safe semantics of checker.clj:54-64 applied per key."""
    from comdb2_tpu.checker.batch import pack_batch, check_batch
    from comdb2_tpu.ops import op as O

    # five processes: wide enough that a 64-transition table is past
    # the keys engine's key, so the last resort is reachable too
    good = ([O.invoke(p, "write", 1) for p in range(5)]
            + [O.ok(p, "write", 1) for p in range(5)])
    bad = [O.invoke(0, "write", 1), O.invoke(0, "write", 2),
           O.ok(0, "write", 2)]
    # build the malformed history by hand: complete() would raise
    bad = [op.with_(index=i) for i, op in enumerate(bad)]
    packed_bad = pack_history(bad, completed=True)
    batch = pack_batch([good, packed_bad, good], M.cas_register())
    for engine, nt in (("keys", 0), ("vmap", last_resort_nt(batch))):
        info = {}
        status, fail_at, n = check_batch(batch, F=32,
                                         n_transitions_pad=nt,
                                         info=info)
        assert info["engine"] == engine, info
        assert status[0] == LJ.VALID and status[2] == LJ.VALID
        assert status[1] == LJ.UNKNOWN, (engine, status)
        assert fail_at[1] == -1 and n[1] == 0


def test_keys_engine_matches_host_fuzz():
    from comdb2_tpu.checker.batch import pack_batch, check_batch

    model = M.cas_register()
    for round_ in range(3):
        histories, want = [], []
        for seed in range(10):
            rng = random.Random(55_000 + round_ * 100 + seed)
            h = histgen.register_history(
                rng, n_procs=rng.randint(2, 4),
                n_events=rng.randint(5, 24),
                p_info=0.1 if seed % 3 == 0 else 0.0)
            if seed % 2:
                h = histgen.mutate(rng, h)
            histories.append(h)
            packed = pack_history(h)
            mm = make_memo(model, packed)
            want.append(linear_host.check(mm, packed).valid)
        batch = pack_batch(histories, model)
        info = {}
        status, fail_at, n = check_batch(batch, F=128, info=info)
        assert info["engine"] == "keys", info
        got = [s == LJ.VALID for s in status]
        assert got == want, (round_, got, want)


def test_device_batch_sharded_mesh():
    import jax
    from jax.sharding import Mesh
    from comdb2_tpu.checker.batch import pack_batch, check_batch

    model = M.cas_register()
    histories = []
    for seed in range(8):
        rng = random.Random(41_000 + seed)
        histories.append(histgen.register_history(rng, n_procs=3,
                                                  n_events=10))
    batch = pack_batch(histories, model)
    mesh = Mesh(np.array(jax.devices()), ("batch",))
    info = {}
    status, fail_at, n = check_batch(batch, F=64, mesh=mesh, info=info)
    assert all(s == LJ.VALID for s in status)
    # the mesh path must ride a fast engine, not the 20x-slower vmap
    # fallback (round-1 Weak #2)
    assert info["engine"] == "keys-sharded", info


def test_sharded_engines_match_solo():
    """Sharded keys runs (8-device CPU mesh, B not divisible by the
    axis) must return the same verdicts and fail indices as the
    single-device engine on mixed valid/invalid/info histories."""
    import jax
    from jax.sharding import Mesh
    from comdb2_tpu.checker.batch import pack_batch, check_batch

    model = M.cas_register()
    histories = []
    for seed in range(13):          # 13 % 8 != 0: exercises padding
        rng = random.Random(72_000 + seed)
        h = histgen.register_history(
            rng, n_procs=rng.randint(2, 4),
            n_events=rng.randint(6, 28),
            p_info=0.1 if seed % 3 == 0 else 0.0)
        if seed % 2:
            h = histgen.mutate(rng, h)
        histories.append(h)
    batch = pack_batch(histories, model)
    mesh = Mesh(np.array(jax.devices()), ("batch",))
    info = {}
    solo_status, solo_fail, solo_n = check_batch(batch, F=64, info=info)
    assert info["engine"] == "keys", info
    info = {}
    status, fail_at, n = check_batch(batch, F=64, mesh=mesh, info=info)
    assert info["engine"] == "keys-sharded", info
    assert status.shape == (13,)
    assert list(status) == list(solo_status), status
    assert list(fail_at) == list(solo_fail), fail_at
    assert list(n) == list(solo_n), n


def test_dedup_survives_sentinel_collisions():
    """Regression: hash-fingerprint dedup collided on rows swapping 0 and
    LIN(-2) across slots, interleaving equal rows and ballooning the
    frontier into spurious overflow. Exact-sort dedup must agree with the
    host engine at the host's true peak frontier size."""
    rng = random.Random(7)
    h = histgen.register_history(rng, n_procs=4, n_events=64, p_info=0.0)
    packed = pack_history(h)
    mm = make_memo(M.cas_register(), packed)
    r = linear_host.check(mm, packed)
    assert r.valid is True
    F = _next_pow2(r.max_frontier)  # tightest power-of-two capacity
    stream = LJ.make_stream(packed)
    status, fail_at, n = LJ.check_device(LJ.pad_succ(mm.succ), *stream,
                                         F=F, P=4)
    assert int(status) == LJ.VALID


def test_chunked_inplace_escalation_matches_monolithic(monkeypatch):
    """Large histories run the chunked engine with IN-PLACE capacity
    escalation: an overflow widens the boundary carry and re-runs only
    the overflowing chunk (a restart would repay every checked chunk
    per ladder level). Forced on via the threshold; verdicts must
    match the monolithic ladder on valid, invalid, and genuinely
    overflowing histories."""
    import random

    from comdb2_tpu.checker import linear
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.op import Op
    from comdb2_tpu.ops.synth import register_history

    rng = random.Random(8)
    valid_h = register_history(rng, n_procs=4, n_events=600, values=4,
                               p_info=0.05)
    invalid_h = list(valid_h)
    for i in range(len(invalid_h) - 1, -1, -1):
        if invalid_h[i].type == "ok" and invalid_h[i].f == "read":
            invalid_h[i] = invalid_h[i].with_(value=99)
            break
    # frontier needs > 8 configs early on (3 pending writers), so the
    # first capacity level must overflow and escalate mid-history
    caps = (8, 256)

    orig_threshold = linear.CHUNKED_S_THRESHOLD
    for h in (valid_h, invalid_h):
        mono = linear.analysis(cas_register(), h, backend="device",
                               capacities=caps)
        monkeypatch.setattr(linear, "CHUNKED_S_THRESHOLD", 4)
        chunked = linear.analysis(cas_register(), h, backend="device",
                                  capacities=caps)
        monkeypatch.setattr(linear, "CHUNKED_S_THRESHOLD",
                            orig_threshold)
        assert chunked.valid == mono.valid, (chunked.info, mono.info)
        if not chunked.valid:
            assert chunked.op_index == mono.op_index

    # exhausted ladder still yields UNKNOWN: many concurrent pending
    # writers blow past the last capacity
    hard = []
    for p in range(10):
        hard.append(Op(process=p, type="invoke", f="write", value=p,
                       time=p))
    hard.append(Op(process=11, type="invoke", f="read", value=None,
                   time=20))
    hard.append(Op(process=11, type="ok", f="read", value=3, time=21))
    monkeypatch.setattr(linear, "CHUNKED_S_THRESHOLD", 4)
    a = linear.analysis(cas_register(), hard, backend="device",
                        capacities=(8, 16))
    assert a.valid == "unknown", a.info


# --- wide-P multi-word packed dedup (round-3 VERDICT #1) --------------------

def test_make_pack_plan_widths():
    """W grows with P; the top word leaves bits 29/30 for flags; every
    field fits its word."""
    for n_states, n_tr, P in ((6, 28, 18), (6, 28, 24), (6, 28, 32),
                              (16, 16, 5), (1 << 20, 4, 3)):
        plan = LJ.make_pack_plan(n_states, n_tr, P)
        assert plan is not None
        used = [0] * plan.n_words
        widths = [plan.state_bits] + [plan.slot_bits] * P
        for w_, (word, shift) in zip(widths, plan.assign):
            assert shift + w_ <= 31
            used[word] = max(used[word], shift + w_)
        assert used[-1] <= 29          # flag space in the top word
    # a single field wider than 29 bits can't pack
    assert LJ.make_pack_plan(1 << 30, 4, 2) is None


def test_pack_plan_words_injective():
    """Distinct configs must map to distinct word tuples at every P the
    plan accepts — including P far beyond the two-word budget."""
    import jax.numpy as jnp

    rng = random.Random(11)
    for n_states, n_tr, P in ((6, 28, 18), (6, 28, 32), (50, 100, 24)):
        plan = LJ.make_pack_plan(n_states, n_tr, P)
        assert plan is not None
        assert not LJ.pack_bits(n_states, n_tr, P)[2]   # 2 words can't
        seen = set()
        configs = []
        for _ in range(300):
            c = (rng.randrange(n_states),
                 tuple(rng.randrange(-2, n_tr) for _ in range(P)))
            if c not in seen:
                seen.add(c)
                configs.append(c)
        states = jnp.asarray([c[0] for c in configs], jnp.int32)
        slots = jnp.asarray([c[1] for c in configs], jnp.int32)
        words = LJ._pack_plan_words(states, slots, plan)
        packed = set(zip(*(np.asarray(w).tolist() for w in words)))
        assert len(packed) == len(configs)


@pytest.mark.parametrize("P", [18, 24, 32])
def test_wide_p_device_matches_host(P):
    """Concurrency far beyond the 62-bit key budget: the multi-word
    packed dedup must agree with the host engine (valid, invalid, and
    fail index). The reference has no width limit at all
    (knossos/linear/config.clj:157-295; CLI default concurrency 30,
    cli.clj:52-91)."""
    model = M.cas_register()
    rng = random.Random(4200 + P)
    h = histgen.register_history(rng, n_procs=P, n_events=140,
                                 values=4, p_info=0.0, max_pending=6)
    for variant in (h, histgen.mutate(rng, h)):
        packed = pack_history(variant)
        mm = make_memo(model, packed)
        r = linear_host.check(mm, packed, max_configs=1 << 20)
        a = analysis(model, packed, backend="device",
                     capacities=(512, 2048))
        assert a.info.get("backend") == "device"
        assert a.valid == r.valid, (P, a.valid, r.valid)
        if r.valid is False:
            assert a.op_index == r.op_index
