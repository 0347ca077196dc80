"""Golden parity: the columnar ingest is BIT-IDENTICAL to the per-op
packer on every fuzz-corpus family.

The columnar rebuild (ops/columnar.py, the vectorized
``make_segments``, ``remap_slots_batch``) replaces the per-op host
walk that cost ``host_pack_s = 278.2`` at the 4096x bench shape. Its
contract is exact equality — same arrays, same table orders, same
segment streams, same renamed slots, same PackPlan words — because
UNKNOWN-verdict comparability across engines and releases depends on
the key layout, and a packer that merely "agreed on verdicts" could
silently shift fail indices and frontier contents.

Families: register/cas (incl. p10 + max_pending), keyed, wide-P
pinned, crash-heavy with ``:info`` slot pinning, and the txn
list-append histories — plus the seeded anomaly fixtures.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from comdb2_tpu.checker import linear_jax as LJ
from comdb2_tpu.checker.independent import wrap_keyed_history
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.columnar import pack_history_columnar
from comdb2_tpu.ops.packed import pack_history, pack_history_legacy
from comdb2_tpu.ops.synth import (list_append_history, pinned_wide_history,
                                  register_history, txn_anomaly_history)

ARRAYS = ("process", "type", "f", "value", "trans", "pair", "fails",
          "time")
TABLES = ("process_table", "f_table", "value_table",
          "transition_table")


def _keyed_history(rng):
    h = []
    for _ in range(30):
        k = rng.randrange(3)
        p = rng.randrange(4)
        v = rng.randrange(3)
        h.append(O.invoke(p, "write", (k, v)))
        h.append(O.ok(p, "write", (k, v)))
    return wrap_keyed_history(h)


def _families():
    rng = random.Random(606)
    yield "register", register_history(rng, n_procs=5, n_events=300,
                                       values=5, p_info=0.0)
    yield "cas-p10", register_history(rng, n_procs=10, n_events=300,
                                      values=5, p_info=0.0,
                                      max_pending=5)
    yield "crash-heavy", register_history(rng, n_procs=4, n_events=300,
                                          values=3, p_info=0.3)
    yield "keyed", _keyed_history(rng)
    yield "wide-p-pinned", pinned_wide_history(18)
    yield "txn-list-append", list_append_history(rng, n_procs=3,
                                                 n_txns=40)
    for kind in ("clean", "g0", "g1c", "g1a", "g2-item", "duplicate"):
        yield f"txn-{kind}", txn_anomaly_history(kind)


FAMILIES = list(_families())


def _assert_packed_equal(a, b, ctx):
    for f in ARRAYS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype, (ctx, f, x.dtype, y.dtype)
        assert np.array_equal(x, y), (ctx, f)
    for f in TABLES:
        assert getattr(a, f) == getattr(b, f), (ctx, f)


def _assert_stream_equal(a, b, ctx):
    for f in a._fields:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype, (ctx, f, x.dtype, y.dtype)
        assert x.shape == y.shape, (ctx, f, x.shape, y.shape)
        assert np.array_equal(x, y), (ctx, f)


@pytest.mark.parametrize("name,hist", FAMILIES,
                         ids=[n for n, _ in FAMILIES])
def test_pack_bit_identical(name, hist):
    legacy = pack_history_legacy(hist)
    col = pack_history_columnar(hist)
    _assert_packed_equal(legacy, col, name)
    # the lazy .ops view materializes the SAME completed indexed list
    assert col.ops == legacy.ops


@pytest.mark.parametrize("name,hist", FAMILIES,
                         ids=[n for n, _ in FAMILIES])
def test_segments_and_remap_bit_identical(name, hist):
    packed = pack_history(hist)
    for s_pad, k_pad in ((None, None), (64, 8)):
        a = LJ.make_segments_legacy(packed, s_pad=s_pad, k_pad=k_pad)
        b = LJ.make_segments(packed, s_pad=s_pad, k_pad=k_pad)
        _assert_stream_equal(a, b, (name, s_pad, k_pad))
    segs = LJ.make_segments(packed)
    want_s, want_p = LJ.remap_slots(segs)
    (got_s,), (got_p,) = LJ.remap_slots_batch([segs])
    _assert_stream_equal(want_s, got_s, name)
    assert want_p == got_p
    # PackPlan words: equal tables => equal plans => equal packed keys
    plan_a = LJ.make_pack_plan(16, packed.n_transitions, want_p or 1)
    plan_b = LJ.make_pack_plan(16, packed.n_transitions, got_p or 1)
    assert plan_a == plan_b


def test_remap_batch_heterogeneous_equals_per_history():
    """One batched call over MIXED families/shapes must reproduce the
    per-history remap exactly (the batch path pads to the widest
    stream; padding must never leak into allocations)."""
    streams = []
    for _, hist in FAMILIES:
        streams.append(LJ.make_segments(pack_history(hist)))
    want = [LJ.remap_slots(s) for s in streams]
    got_s, got_p = LJ.remap_slots_batch(streams)
    for (ws, wp), gs, gp, (name, _) in zip(want, got_s, got_p,
                                           FAMILIES):
        _assert_stream_equal(ws, gs, name)
        assert wp == gp, name


def test_stream_segments_legacy_flag_parity(monkeypatch):
    """The COMDB2_TPU_LEGACY_PACK=1 escape hatch routes the whole
    ingest through the per-op implementations — and produces the
    exact same streams and P_eff as the columnar default."""
    from comdb2_tpu.checker.batch import _stream_segments, pack_batch
    from comdb2_tpu.models.model import cas_register

    hists = [h for name, h in FAMILIES
             if name.startswith(("register", "cas", "crash"))]
    col_batch = pack_batch([list(h) for h in hists], cas_register())
    col_streams, col_p = _stream_segments(col_batch)

    monkeypatch.setenv("COMDB2_TPU_LEGACY_PACK", "1")
    leg_batch = pack_batch([list(h) for h in hists], cas_register())
    leg_streams, leg_p = _stream_segments(leg_batch)
    assert col_p == leg_p
    for i, (a, b) in enumerate(zip(leg_streams, col_streams)):
        _assert_stream_equal(a, b, i)


def test_error_class_parity():
    dbl = [O.invoke(0, "read", None), O.invoke(0, "write", 1)]
    with pytest.raises(RuntimeError):
        pack_history_columnar(dbl)
    with pytest.raises(RuntimeError):
        pack_history_legacy(dbl)
    orphan = [O.ok(0, "read", 1)]
    with pytest.raises(RuntimeError):
        pack_history_columnar(orphan)
    with pytest.raises(RuntimeError):
        pack_history_legacy(orphan)
    mismatch = [O.invoke(0, "write", 1), O.fail(0, "write", 2)]
    with pytest.raises(RuntimeError):
        pack_history_columnar(mismatch)
    with pytest.raises(RuntimeError):
        pack_history_legacy(mismatch)
    # completed=True keeps the pack loop's overwrite semantics
    bad = [op.with_(index=i) for i, op in enumerate(
        [O.invoke(0, "write", 1), O.invoke(0, "write", 2),
         O.ok(0, "write", 2)])]
    _assert_packed_equal(pack_history_legacy(bad, completed=True),
                         pack_history_columnar(bad, completed=True),
                         "double-pending")


def test_columnar_generator_roundtrip_and_validity():
    """The whole-batch generator's arrays must be exactly what the
    LEGACY packer produces from its own materialized ops (interning
    order, pairing, transitions), and every history must be
    linearizable under the host oracle."""
    from comdb2_tpu.checker import linear_host
    from comdb2_tpu.models.memo import memo
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.synth_columnar import register_batch_packed

    ps = register_batch_packed(42, 12, 40, n_procs=4, values=3,
                               p_info=0.15)
    for i, p in enumerate(ps):
        _assert_packed_equal(pack_history_legacy(p.ops), p,
                             ("gen", i))
        r = linear_host.check(memo(cas_register(), p), p)
        assert r.valid is True, (i, r)


def test_check_batch_verdict_parity_legacy_vs_columnar(monkeypatch):
    """End-to-end: a mixed valid/invalid batch reaches identical
    (status, fail_at, n_final) through both ingest paths."""
    from comdb2_tpu.checker.batch import check_batch, pack_batch
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.synth import mutate

    rng = random.Random(99)
    hs = []
    for i in range(6):
        h = register_history(rng, n_procs=3, n_events=40, values=3,
                             p_info=0.0)
        hs.append(mutate(rng, h) if i % 2 else h)
    info: dict = {}
    col = check_batch(pack_batch([list(h) for h in hs],
                                 cas_register()), F=64, info=info)
    assert info["engine"] == "keys"
    monkeypatch.setenv("COMDB2_TPU_LEGACY_PACK", "1")
    leg = check_batch(pack_batch([list(h) for h in hs],
                                 cas_register()), F=64)
    for a, b in zip(col, leg):
        assert np.array_equal(np.asarray(a), np.asarray(b))
