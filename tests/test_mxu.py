"""MXU frontier engine (checker/mxu) — BFS-as-matmul for wide P.

Contracts:

- bit-exact verdict parity with the host oracle and the XLA seg
  engine on overlapping (P <= 15) shapes;
- a genuinely concurrent wide-P bounded-in-flight history that
  overflows the XLA engine's frontier cap gets a DEFINITE verdict
  from the MXU engine (the scaled tier-1 proxy of the bench's
  65536 -> 131072 crossing);
- in-place capacity escalation (``expand_carry``) resumes at the
  overflowing chunk and reproduces the single-dispatch verdict;
- the driver ladder routes wide P to the engine (``engine ==
  "mxu-frontier"``) and the batch path auto-picks it;
- UNKNOWN artifacts name the engine + capacity that gave up
  (``cause`` / ``engines_tried`` — the round-10 attribution fix);
- observed lowerings stay inside the PROGRAMS.md inventory.
"""

import random

import numpy as np
import pytest

from comdb2_tpu.checker import analysis
from comdb2_tpu.checker import linear_host, linear_jax as LJ
from comdb2_tpu.checker import mxu as MXU
from comdb2_tpu.checker.linear import _next_pow2
from comdb2_tpu.models.memo import memo as make_memo
from comdb2_tpu.models import model as M
from comdb2_tpu.ops import synth_columnar as SC
from comdb2_tpu.ops.packed import pack_history

import histgen


def _prep(model, h, s_pad=32, k_pad=4):
    """pack -> memo -> bucketed, slot-renamed segments (the driver's
    shape discipline, so the suite shares a few compiled programs)."""
    packed = h if not isinstance(h, list) else pack_history(h)
    mm = make_memo(model, packed)
    segs = LJ.make_segments(packed, s_pad=s_pad, k_pad=k_pad)
    segs, p_eff = LJ.remap_slots(segs)
    succ = LJ.pad_succ(mm.succ, _next_pow2(mm.n_states),
                       _next_pow2(mm.n_transitions))
    return packed, mm, segs, succ, max(p_eff, 1)


def _mxu(mm, segs, succ, P, F=128):
    st, fa, n = MXU.check_device_mxu(
        succ, segs.inv_proc, segs.inv_tr, segs.ok_proc, segs.depth,
        F=F, P=P, n_states=mm.n_states, n_transitions=mm.n_transitions)
    return int(st), int(fa), int(n)


# --- parity on overlapping P <= 15 shapes ----------------------------------

@pytest.mark.parametrize("seed", range(25))
def test_matches_host_and_xla_random(seed):
    """Verdict + fail-index + final-count parity against the host
    oracle AND the XLA seg engine on small register histories (the
    engines must be bit-exact, not merely verdict-equal)."""
    rng = random.Random(88_000 + seed)
    h = histgen.register_history(rng, n_procs=rng.randint(2, 4),
                                 n_events=rng.randint(6, 24),
                                 p_info=0.1)
    if rng.random() < 0.6:
        h = histgen.mutate(rng, h)
    packed, mm, segs, succ, P = _prep(M.cas_register(), h)
    st, fa, n = _mxu(mm, segs, succ, P)
    st2, fa2, n2 = LJ.check_device_seg(
        succ, segs.inv_proc, segs.inv_tr, segs.ok_proc, segs.depth,
        F=128, P=P, n_states=mm.n_states,
        n_transitions=mm.n_transitions)
    # the cross-engine contract (CLAUDE.md): counts compare on VALID
    # verdicts only — on INVALID the seg engine zeroes its count while
    # the flat-layout engines (mxu included) keep the pre-death one
    assert st == int(st2)
    if st == LJ.VALID:
        assert n == int(n2)
    else:
        assert fa == int(fa2)
    hr = linear_host.check(mm, packed)
    assert st in (LJ.VALID, LJ.INVALID)
    assert (st == LJ.VALID) == hr.valid
    if st == LJ.INVALID:
        assert int(segs.seg_index[fa]) == hr.op_index


def test_wide_p_generator_parity_small():
    """The wave generator's valid + violation twins, cross-checked
    against the host oracle at P = 16 (small free-read count keeps the
    frontier tiny)."""
    for violation in (False, True):
        ps = SC.wide_register_batch_packed(
            31, 2, n_waves=2, n_chain=12, n_free=4, values=16,
            violation=violation)
        for p in ps:
            packed, mm, segs, succ, P = _prep(M.cas_register(), p)
            assert P == 16          # genuinely concurrent: P_eff = P
            st, fa, _ = _mxu(mm, segs, succ, P, F=1024)
            hr = linear_host.check(mm, packed)
            assert hr.valid is (not violation)
            assert (st == LJ.VALID) == hr.valid
            if st == LJ.INVALID:
                assert int(segs.seg_index[fa]) == hr.op_index


def test_wide_p_generator_rejects_unseedable_violation():
    """``violation=True`` needs a free read to seed — with n_free=0
    the twin would silently be a valid history (a harness's
    'violation => INVALID' assertion would then fail far from the
    cause), so the constructor refuses."""
    with pytest.raises(ValueError, match="n_free >= 1"):
        SC.wide_register_batch_columns(31, 1, n_waves=2, n_chain=16,
                                       n_free=0, values=18,
                                       violation=True)


# --- the workload-class conversion: XLA cap overflow -> MXU verdict --------

def _wide_case(n_free=9, violation=False):
    ps = SC.wide_register_batch_packed(
        47, 1, n_waves=2, n_chain=7, n_free=n_free, values=16,
        violation=violation)
    return _prep(M.cas_register(), ps[0], s_pad=64, k_pad=4)


def test_wide_p_unknown_becomes_verdict():
    """A P=16 bounded-in-flight history whose free-read subset
    frontier (2^9 + chain) overflows the XLA engine at its capacity
    rung gets a DEFINITE verdict from the MXU engine at the next rung
    — the scaled proxy of the bench's 65536 -> 131072 crossing."""
    packed, mm, segs, succ, P = _wide_case()
    assert P == 16
    st_x, _, _ = LJ.check_device_seg(
        succ, segs.inv_proc, segs.inv_tr, segs.ok_proc, segs.depth,
        F=256, P=P, n_states=mm.n_states,
        n_transitions=mm.n_transitions)
    assert int(st_x) == LJ.UNKNOWN      # 2^9 free-read subsets > 256
    st, _, n = _mxu(mm, segs, succ, P, F=1024)
    assert st == LJ.VALID and n >= 1
    # and the violation twin dies with a definite INVALID, not UNKNOWN
    packed, mm, segs, succ, P = _wide_case(violation=True)
    st, fa, _ = _mxu(mm, segs, succ, P, F=1024)
    assert st == LJ.INVALID
    hr = linear_host.check(mm, packed)
    assert int(segs.seg_index[fa]) == hr.op_index


def test_chunked_expand_carry_escalates_in_place():
    """The chunk form resumes from a widened PRE-chunk carry: F=64
    overflows, expand_carry(1024) re-runs only the chunk, and the
    verdict matches the single-dispatch engine."""
    packed, mm, segs, succ, P = _wide_case()
    sizes = dict(n_states=mm.n_states, n_transitions=mm.n_transitions)
    want = _mxu(mm, segs, succ, P, F=1024)
    S = segs.ok_proc.shape[0]
    chunk = 32
    F = 64
    carry = MXU.init_carry(1, F, P, **sizes)
    done = 0
    escalated = False
    while done < S:
        end = done + chunk
        new_carry = MXU.check_device_mxu_chunk(
            succ, segs.inv_proc[done:end], segs.inv_tr[done:end],
            segs.ok_proc[done:end], segs.depth[done:end], done,
            carry, F=F, P=P, **sizes)
        if int(new_carry[3][0]) == LJ.UNKNOWN and F < 1024:
            F = 1024
            carry = MXU.expand_carry(carry, F)
            escalated = True
            continue                # same chunk, wider frontier
        carry = new_carry
        done = end
        if int(carry[3][0]) != LJ.VALID:
            break
    assert escalated, "the F=64 rung should have overflowed"
    got = (int(carry[3][0]), int(carry[4][0]), int(carry[2][0]))
    assert got == want


def test_driver_routes_wide_p_to_mxu():
    """End to end through ``analysis``: wide-P valid/violation twins
    ride the MXU arm, with engine attribution in the artifact."""
    for violation in (False, True):
        ps = SC.wide_register_batch_packed(
            53, 1, n_waves=2, n_chain=13, n_free=3, values=16,
            violation=violation)
        a = analysis(M.cas_register(), ps[0], backend="device",
                     host_threshold=1)
        assert a.info["engine"] == "mxu-frontier"
        assert a.info["frontier_capacity"] in MXU.CAPACITIES
        assert a.valid is (not violation)


def test_driver_chunked_progress_and_histogram():
    """The chunked driver arm (forced by a progress callback) must
    reproduce the non-chunked verdict and report telemetry through
    the MXU pending histogram."""
    ps = SC.wide_register_batch_packed(59, 1, n_waves=3, n_chain=14,
                                       n_free=2, values=17)
    ticks = []

    def progress(done, total, count, stats):
        ticks.append((done, total, count, stats))

    a = analysis(M.cas_register(), ps[0], backend="device",
                 host_threshold=1, progress=progress,
                 progress_interval_s=0.0)
    assert a.valid is True
    assert a.info["engine"] == "mxu-frontier"
    assert ticks and all(t[1] >= t[0] > 0 for t in ticks)
    assert all("est_cost" in t[3] for t in ticks)


def test_unknown_artifact_names_engine_and_capacity(monkeypatch):
    """The attribution fix: a capacity give-up must say WHICH engine
    overflowed at WHAT capacity — a wide-P UNKNOWN and an XLA
    capacity abort used to render identically."""
    monkeypatch.setattr(MXU, "CAPACITIES", (64,))
    ps = SC.wide_register_batch_packed(61, 1, n_waves=2, n_chain=8,
                                       n_free=8, values=16)
    a = analysis(M.cas_register(), ps[0], backend="device",
                 host_threshold=1)
    assert a.valid == "unknown"
    assert "mxu-frontier" in a.info["cause"]
    assert "64" in a.info["cause"]
    # the XLA arm attributes the same way (narrow P, tiny ladder)
    h = []
    import comdb2_tpu.ops.op as O
    for i in range(8):
        h.append(O.invoke(i, "write", i))
        h.append(O.info(i, "write", i))
    h += [O.invoke(100, "read", None), O.ok(100, "read", 5)]
    a2 = analysis(M.register(), h, backend="device",
                  host_threshold=1, capacities=(16,))
    assert a2.valid == "unknown"
    assert "xla-seg2" in a2.info["cause"]


def test_capacities_bounds_mxu_ladder(monkeypatch):
    """``analysis(capacities=...)`` bounds the MXU arm too: each entry
    buckets up to the engine's declared rungs and the ladder stops at
    the caller's bound — a caller limiting device work can force an
    early UNKNOWN instead of silently escalating to the top rung."""
    monkeypatch.setattr(MXU, "CAPACITIES", (64, 256))
    ps = SC.wide_register_batch_packed(61, 1, n_waves=2, n_chain=9,
                                       n_free=7, values=16)
    # peak frontier ~ n_chain + 2^n_free = 137: past 64, inside 256.
    # A 16-bound buckets to the 64 rung ONLY — overflow there is final
    a = analysis(M.cas_register(), ps[0], backend="device",
                 host_threshold=1, capacities=(16,))
    assert a.valid == "unknown"
    assert a.info["engine"] == "mxu-frontier"
    assert "64" in a.info["cause"]
    # a bound that buckets onto the wider rung gets the verdict there
    a2 = analysis(M.cas_register(), ps[0], backend="device",
                  host_threshold=1, capacities=(16, 100))
    assert a2.valid is True
    assert a2.info["frontier_capacity"] == 256


# --- gating ----------------------------------------------------------------

def test_serves_gating(monkeypatch):
    assert MXU.serves(32, 32, 16)
    assert not MXU.serves(32, 32, 15)        # fused-kernel territory
    assert not MXU.serves(512, 32, 16)       # past S_CAP
    assert not MXU.serves(32, 256, 16)       # past T_CAP
    assert not MXU.serves(32, 32, MXU.MAX_P + 1)
    assert MXU.fits(32, 32, 4)               # fits() has no P floor:
    monkeypatch.setenv("COMDB2_TPU_MXU", "0")  # parity paths use it
    assert not MXU.serves(32, 32, 16)


def test_env_kill_switch_routes_back_to_xla(monkeypatch):
    monkeypatch.setenv("COMDB2_TPU_MXU", "0")
    ps = SC.wide_register_batch_packed(53, 1, n_waves=2, n_chain=13,
                                       n_free=3, values=16)
    a = analysis(M.cas_register(), ps[0], backend="device",
                 host_threshold=1)
    assert a.valid is True
    assert a.info["engine"] == "xla-seg2"


# --- batch path ------------------------------------------------------------

def test_batch_auto_picks_mxu_and_matches_driver():
    from comdb2_tpu.checker.batch import check_batch, pack_batch

    ps = SC.wide_register_batch_packed(67, 3, n_waves=2, n_chain=14,
                                       n_free=2, values=17)
    bad = SC.wide_register_batch_packed(67, 1, n_waves=2, n_chain=14,
                                        n_free=2, values=17,
                                        violation=True)
    batch = pack_batch(ps + bad, M.cas_register())
    info = {}
    st, fa, nf = check_batch(batch, F=1024, info=info)
    assert info["engine"] == "mxu"
    assert st.tolist() == [LJ.VALID] * 3 + [LJ.INVALID]
    # the INVALID lane's fail index matches the host oracle
    mm = make_memo(M.cas_register(), bad[0])
    hr = linear_host.check(mm, bad[0])
    assert int(fa[3]) == hr.op_index


def test_batch_lowerings_stay_inside_inventory():
    """The runtime compile guard agrees with the static inventory on
    the REAL mxu lowerings (eval_shape witnesses alone can drift)."""
    from comdb2_tpu.analysis.compile_surface import static_inventory
    from comdb2_tpu.checker.batch import check_batch, pack_batch
    from comdb2_tpu.utils import compile_guard as CG

    ps = SC.wide_register_batch_packed(71, 2, n_waves=2, n_chain=14,
                                       n_free=2, values=17)
    batch = pack_batch(ps, M.cas_register())
    info = {}
    with CG.guard() as g:
        st, _, _ = check_batch(batch, F=1024, info=info)
    assert info["engine"] == "mxu"
    assert st.tolist() == [LJ.VALID] * 2
    g.assert_closed(static_inventory())
