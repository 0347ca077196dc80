"""Mesh-vs-single-device bit-parity suite (8-device CPU mesh).

The shard-placement axis must be INVISIBLE in every verdict: sharded
and unsharded runs of the same batch return identical (status,
fail_at, n_final) across the register/cas, keyed, txn-closure and
shrink surfaces — including B not divisible by D (sentinel padding),
kernel escalation mid-batch on one shard, and the compile guard
proving observed lowerings stay inside the shard-extended
PROGRAMS.md inventory. The fused kernel's sharded semantics run here
through Pallas interpret mode (exact kernel as XLA ops; Mosaic is
TPU-only) — the real-chip twin is ``python chip_smoke.py --multichip``
and the ``multichip`` stage of ``check.sh``.
"""

import random

import numpy as np
import pytest

import histgen
from engine_oracles import dense_streams, keys_verdicts, last_resort_nt
from comdb2_tpu.checker import batch as CB
from comdb2_tpu.checker import linear_jax as LJ
from comdb2_tpu.checker import pallas_seg as PSEG
from comdb2_tpu.checker.batch import check_batch, pack_batch
from comdb2_tpu.models import model as M


def _mesh(n=8):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]), ("batch",))


def _mixed_histories(n, seed=72_000, keyed=False):
    hs = []
    for i in range(n):
        rng = random.Random(seed + i)
        h = histgen.register_history(
            rng, n_procs=rng.randint(2, 4),
            n_events=rng.randint(6, 28),
            p_info=0.1 if i % 3 == 0 else 0.0)
        if i % 2:
            h = histgen.mutate(rng, h)
        hs.append(h)
    return hs


# --- pure planning helpers ---------------------------------------------


def test_plan_shard_slices_layout():
    # 16 histories over 8 shards, cap 512: one slice, shard d owns
    # [2d, 2d+2)
    assert PSEG.plan_shard_slices(16, 8) == [(0, 16)]
    # per-shard cap 2 -> two slices, each 8*2 wide
    assert PSEG.plan_shard_slices(32, 8, max_stream_b=2) == \
        [(0, 16), (16, 32)]
    with pytest.raises(ValueError):
        PSEG.plan_shard_slices(10, 8)       # not a multiple of D


def test_merge_stream_shards_reassembles_slice_order():
    D, g = 4, 3
    res = np.zeros((D, 8, 3), np.int32)
    starts = []
    want = []
    k = 0
    for d in range(D):
        st = np.arange(g, dtype=np.int64) * 5
        for i in range(g):
            res[d, i] = (k % 3, (k % 2) * 2 + st[i] if k % 2 else -1,
                         k)
            want.append((k % 3, 2 if k % 2 else -1, k))
            k += 1
        starts.append(st)
    out = PSEG.merge_stream_shards(res[:, :, :], starts, D * g, D)
    assert out == want


# --- register/cas + keyed parity over the XLA sharded engines ----------


@pytest.mark.parametrize("engine", ["keys", "vmap"])
def test_register_parity_b_not_divisible(engine):
    """13 mixed valid/invalid/info histories over 8 shards: verdicts
    bit-identical with the single-device run, pads invisible. The
    last resort ("vmap", reached through a table too wide for the
    keys engine's key) runs one device under the mesh and says so,
    and agrees with the keys engine's verdicts."""
    batch = pack_batch(_mixed_histories(13), M.cas_register())
    nt = last_resort_nt(batch) if engine == "vmap" else 0
    solo_info: dict = {}
    solo = check_batch(batch, F=64, n_transitions_pad=nt,
                       info=solo_info)
    assert solo_info["engine"] == engine
    info: dict = {}
    st, fa, n = check_batch(batch, F=64, mesh=_mesh(),
                            n_transitions_pad=nt, info=info)
    if engine == "vmap":
        assert info["engine"] == "vmap" and info["mesh_dropped"]
        keys = keys_verdicts(batch, 64)
        np.testing.assert_array_equal(st, keys[0])
        np.testing.assert_array_equal(fa, keys[1])
        ok = st == LJ.VALID
        np.testing.assert_array_equal(n[ok], keys[2][ok])
    else:
        assert info["engine"] == "keys-sharded"
        assert "mesh_dropped" not in info
    assert info["batch"] == {"b": 13, "b_pad": 16, "pad": 3,
                             "shards": 8}
    assert st.shape == (13,)            # pads can never surface
    np.testing.assert_array_equal(st, solo[0])
    np.testing.assert_array_equal(fa, solo[1])
    np.testing.assert_array_equal(n, solo[2])


def test_register_parity_vs_vmap_oracle():
    """The retired vmap-sharded route survives as a TEST ORACLE — an
    independent sharded lowering the production engines must agree
    with (the round-7 contract for keeping it)."""
    batch = pack_batch(_mixed_histories(8, seed=81_000),
                       M.cas_register())
    succ = LJ.pad_succ(batch.memo.succ,
                       1 << (batch.memo.n_states - 1).bit_length(),
                       1 << (batch.memo.n_transitions - 1).bit_length())
    P = max(batch.P, 2) + (max(batch.P, 2) & 1)
    st_o, _, n_o = (np.asarray(x) for x in LJ.check_sharded(
        _mesh(), succ, *dense_streams(batch), F=64, P=P,
        n_states=batch.memo.n_states,
        n_transitions=batch.memo.n_transitions))
    info: dict = {}
    st, _, n = check_batch(batch, F=64, mesh=_mesh(), info=info)
    assert info["engine"] == "keys-sharded"
    np.testing.assert_array_equal(st, st_o)
    ok = st == LJ.VALID
    np.testing.assert_array_equal(n[ok], n_o[ok])


def test_keyed_parity():
    """Keyed (independent per-key) histories through the mesh: the
    keyed wrap splits one multi-key history into per-key
    sub-histories — exactly the batch axis the mesh shards."""
    from comdb2_tpu.checker.independent import (history_keys,
                                                subhistory,
                                                wrap_keyed_history)
    from comdb2_tpu.ops import op as O

    rng = random.Random(4242)
    ops = []
    for i in range(120):
        k = rng.randrange(6)
        p = rng.randrange(3)
        v = rng.randrange(3)
        ops.append(O.invoke(p, "write", (k, v)))
        ops.append(O.ok(p, "write", (k, v)))
    wrapped = wrap_keyed_history(ops)
    subs = [subhistory(k, wrapped) for k in history_keys(wrapped)]
    assert len(subs) >= 4
    batch = pack_batch(subs, M.cas_register())
    solo = check_batch(batch, F=64)
    info: dict = {}
    st, fa, n = check_batch(batch, F=64, mesh=_mesh(), info=info)
    assert info["engine"] == "keys-sharded"
    np.testing.assert_array_equal(st, solo[0])
    np.testing.assert_array_equal(fa, solo[1])
    np.testing.assert_array_equal(n, solo[2])


def test_all_shard_sizes_match():
    """D in {1, 2, 4, 8}: every mesh width returns the same verdicts
    (dispatch-width scaling changes shapes, never answers)."""
    batch = pack_batch(_mixed_histories(11, seed=90_000),
                       M.cas_register())
    solo = check_batch(batch, F=64)
    for d in (1, 2, 4, 8):
        info: dict = {}
        st, fa, n = check_batch(batch, F=64, mesh=_mesh(d), info=info)
        assert info["engine"] == "keys-sharded", f"D={d}"
        np.testing.assert_array_equal(st, solo[0], err_msg=f"D={d}")
        np.testing.assert_array_equal(fa, solo[1], err_msg=f"D={d}")
        np.testing.assert_array_equal(n, solo[2], err_msg=f"D={d}")


def test_non_pow2_mesh_rejected():
    with pytest.raises(ValueError, match="power of two"):
        check_batch(pack_batch(_mixed_histories(4), M.cas_register()),
                    F=64, mesh=_mesh(3))


# --- txn closure parity ------------------------------------------------


def test_txn_closure_parity():
    from comdb2_tpu.txn import closure_jax as CJ
    from comdb2_tpu.txn.scc import cyclic_layers_host

    rng = np.random.default_rng(11)
    B, N = 5, 32
    adjs = np.zeros((B, 4, N, N), bool)
    for b in range(B):
        n_edges = int(rng.integers(4, 40))
        for _ in range(n_edges):
            i, j = rng.integers(0, N, 2)
            if i != j:
                adjs[b, int(rng.integers(0, 3)), i, j] = True
    solo = CJ.closure_diag_batch(adjs)
    d0 = CJ.DISPATCHES
    sharded = CJ.closure_diag_batch(adjs, mesh=_mesh())
    assert CJ.DISPATCHES - d0 == 1          # ONE dispatch, all shards
    assert sharded.shape == (B, 3, N)       # pads sliced off
    np.testing.assert_array_equal(sharded, solo)
    # host oracle agrees per graph
    for b in range(B):
        host = cyclic_layers_host(adjs[b], realtime=True)
        np.testing.assert_array_equal(sharded[b], host)


def test_txn_shrink_parity():
    """Txn-granularity minimal-cycle shrink with the verdict buckets
    sharded: same minimal txn set, same certificate. Seed: a write-
    skew rw ring of 8 txns plus an audit read (the -T signature)."""
    from comdb2_tpu.ops import op as O
    from comdb2_tpu.shrink import TxnShrinker

    k = 8
    h = []
    for i in range(k):
        mops = (("r", i, None), ("append", (i + 1) % k, 1))
        done = (("r", i, ()), ("append", (i + 1) % k, 1))
        h.append(O.invoke(i, "txn", mops))
        h.append(O.Op(i, "ok", "txn", done))
    audit = tuple(("r", i, (1,)) for i in range(k))
    h.append(O.invoke(k, "txn",
                      tuple(("r", i, None) for i in range(k))))
    h.append(O.Op(k, "ok", "txn", audit))

    def run(mesh):
        job = TxnShrinker(h, mesh=mesh)
        while not job.step():
            pass
        assert job.error is None
        return job.result()

    solo, sharded = run(None), run(_mesh())
    assert solo.valid is False and sharded.valid is False
    assert sharded.extra["txns"] == solo.extra["txns"]
    assert sharded.one_minimal and solo.one_minimal
    assert sharded.n_ops == solo.n_ops


# --- shrink (linear axis) parity ---------------------------------------


def test_shrink_parity_mesh():
    """Completion-pair ddmin with candidate verdict buckets sharded
    over the mesh: identical minimal history and certificate."""
    from comdb2_tpu.ops.synth import inject_anomaly, register_history
    from comdb2_tpu.shrink import Shrinker

    rng = random.Random(17)
    base = register_history(rng, n_procs=3, n_events=60, p_info=0.0)
    seed, _ = inject_anomaly(base, "stale-read")

    def run(mesh):
        job = Shrinker(seed, "cas-register", F=64, mesh=mesh)
        while not job.step():
            pass
        assert job.error is None
        return job.result()

    solo, sharded = run(None), run(_mesh())
    assert solo.valid is False and sharded.valid is False
    assert sharded.n_ops == solo.n_ops
    assert sharded.one_minimal and solo.one_minimal
    assert [(o.process, o.type, o.f, o.value) for o in sharded.ops] \
        == [(o.process, o.type, o.f, o.value) for o in solo.ops]


# --- sentinel-pad exclusion (satellite: D|B padding accounting) --------


def test_pads_never_surface_anywhere():
    """3 histories over 8 shards: 5 sentinel pads are dispatched but
    can never surface — verdict arrays stay length 3, fail indices
    stay in-history, and the info accounting names the pad factor."""
    hs = _mixed_histories(3, seed=55_000)
    batch = pack_batch(hs, M.cas_register())
    info: dict = {}
    st, fa, n = check_batch(batch, F=64, mesh=_mesh(), info=info)
    assert info["engine"] == "keys-sharded"
    assert info["batch"] == {"b": 3, "b_pad": 8, "pad": 5,
                             "shards": 8}
    assert st.shape == fa.shape == n.shape == (3,)
    for b in range(3):
        assert -1 <= fa[b] < len(batch.packeds[b])


def test_shrink_candidates_exclude_pads():
    """Shrink verdict buckets under the mesh: the status array aligns
    with the requested masks exactly (pad candidates vanish)."""
    from comdb2_tpu.models.memo import memoize_model, transitions_of
    from comdb2_tpu.ops.packed import pack_history
    from comdb2_tpu.ops.synth import inject_anomaly, register_history
    from comdb2_tpu.shrink.verdicts import check_candidates

    rng = random.Random(23)
    seed, _ = inject_anomaly(
        register_history(rng, n_procs=3, n_events=40, p_info=0.0),
        "stale-read")
    parent = pack_history(seed)
    memo = memoize_model(M.cas_register(), transitions_of(parent),
                         max_depth=len(seed))
    full = np.ones(len(parent), bool)
    masks = [full.copy() for _ in range(3)]
    st = check_candidates(parent, masks, memo, F=64, mesh=_mesh())
    assert st.shape == (3,)
    assert (st == LJ.INVALID).all()


# --- the fused kernel on the mesh (interpret mode) ---------------------


@pytest.fixture()
def interpret_kernel():
    PSEG.use_interpret(True)
    yield
    PSEG.use_interpret(False)


def test_stream_sharded_single_dispatch_counters(interpret_kernel):
    """One fused dispatch per slice covering all shards — and the
    Mosaic/XLA program count must NOT scale with D (the per-shard
    body is the same compiled kernel scan)."""
    rng = random.Random(909)
    hs = [histgen.register_history(rng, n_procs=4, n_events=40,
                                   values=3, p_info=0.0)
          for _ in range(4)]
    hs.append(histgen.mutate(rng, hs[0]))
    hs = hs * 2                                     # 10 histories
    batch = pack_batch(hs, M.cas_register())
    d0, m0 = PSEG.DISPATCHES, PSEG.MOSAIC_BUILDS
    info: dict = {}
    st_s, fa_s, n_s = check_batch(batch, F=PSEG.F, mesh=_mesh(),
                                  info=info)
    assert info["engine"] == "stream-sharded"
    assert PSEG.DISPATCHES - d0 == 1        # one slice -> ONE dispatch
    builds_first = PSEG.MOSAIC_BUILDS - m0
    # a second run at another D must reuse the per-shard program
    batch2 = pack_batch(hs, M.cas_register())
    m1 = PSEG.MOSAIC_BUILDS
    info2: dict = {}
    st2, fa2, n2 = check_batch(batch2, F=PSEG.F, mesh=_mesh(4),
                               info=info2)
    assert info2["engine"] == "stream-sharded"
    assert PSEG.MOSAIC_BUILDS - m1 <= builds_first
    np.testing.assert_array_equal(st_s, st2)
    np.testing.assert_array_equal(fa_s, fa2)
    # keys parity (counts compare on VALID only)
    st_k, fa_k, n_k = keys_verdicts(batch, PSEG.F, mesh=_mesh(2))
    np.testing.assert_array_equal(st_s, st_k)
    np.testing.assert_array_equal(fa_s, fa_k)
    ok = st_s == LJ.VALID
    np.testing.assert_array_equal(n_s[ok], n_k[ok])


def test_escalation_mid_batch_on_one_shard(interpret_kernel):
    """One shard's history overflows the kernel's top frontier rung
    (F_TOP) while the other shards stay clean: exactly that history
    re-runs through the XLA sharded engine at the caller's larger F
    and every verdict stays bit-identical with the all-XLA run."""
    from comdb2_tpu.ops import op as O

    def overflow_history(k):
        # 8 writes pending, the first returns, k - 8 more join: at
        # most 8 invokes per segment, k - 1 writes pending together
        h = [O.invoke(p, "write", p) for p in range(8)]
        h += [O.ok(0, "write", 0)]
        h += [O.invoke(p, "write", p) for p in range(8, k)]
        h += [O.ok(p, "write", p) for p in range(1, k)]
        return h

    rng = random.Random(13)
    hs = [histgen.register_history(rng, n_procs=4, n_events=24,
                                   p_info=0.0) for _ in range(7)]
    hs.append(overflow_history(11))         # 5248-config closure
    F = 2 * PSEG.F_TOP
    batch = pack_batch(hs, M.cas_register())
    info: dict = {}
    st, fa, n = check_batch(batch, F=F, mesh=_mesh(), info=info)
    assert info["engine"] == "stream-sharded"
    esc = info.get("escalated")
    assert esc and esc["count"] == 1 and esc["engine"], info
    # 11 processes pad the batch's slot axis to 16, past the keys
    # engine's 62-bit layout: the MXU engine is the all-XLA oracle,
    # which check_batch picks once the kernel is off
    PSEG.use_interpret(False)
    solo_info: dict = {}
    solo = check_batch(pack_batch(hs, M.cas_register()), F=F,
                       info=solo_info)
    assert solo_info["engine"] == "mxu"
    np.testing.assert_array_equal(st, solo[0])
    np.testing.assert_array_equal(fa, solo[1])


# --- compile guard over the shard-extended inventory -------------------


def test_guard_closed_over_mesh_workload():
    """Mixed sharded check/txn/shrink traffic under the guard:
    observed lowerings ⊆ the shard-extended PROGRAMS.md inventory."""
    from comdb2_tpu.analysis import compile_surface as CS
    from comdb2_tpu.txn import closure_jax as CJ
    from comdb2_tpu.utils import compile_guard as CG
    from comdb2_tpu.utils import next_pow2

    inv = CS.static_inventory()
    mesh = _mesh()
    with CG.guard() as g:
        for n_ev, B in ((24, 5), (48, 13)):
            hs = _mixed_histories(B, seed=30_000 + n_ev)
            batch = pack_batch(hs, M.cas_register())
            ns = next_pow2(batch.memo.n_states)
            nt = next_pow2(batch.memo.n_transitions)
            info: dict = {}
            check_batch(batch, F=64, mesh=mesh, s_pad=8, k_pad=2,
                        n_states_pad=ns, n_transitions_pad=nt,
                        info=info)
            assert info["engine"] == "keys-sharded"
        CJ.closure_diag_batch(np.zeros((3, 4, 32, 32), bool),
                              mesh=mesh)
    off = g.offenders(inv)
    assert off == [], [r.format() for r in off]
    g.assert_closed(inv)
    names = {r.name for r in g.records}
    assert "check_device_keys_sharded" in names \
        or not g.records            # warm persistent cache: no logs?
