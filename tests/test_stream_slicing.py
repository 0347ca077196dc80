"""Multi-device stream slicing as pure functions (round-2 Weak #2).

The streamed kernel's slice assignment, per-slice verdict merge, and
UNKNOWN-escalation previously ran with more than one device exactly
nowhere: the kernel doesn't lower on CPU, the multichip dryrun
validates only the keys-sharded XLA path, and the real bench has one
chip. The logic now lives in pure functions
(``pallas_seg.plan_stream_slices`` / ``merge_stream_slice``,
``batch.escalation_indices`` / ``merge_escalation``) exercised here on
CPU with fake device lists and fake result buffers — plus the full
escalation WIRING in ``check_batch`` driven through a faked stream
engine, with the escalated history resolved by the real XLA ladder."""

import numpy as np
import pytest

from comdb2_tpu.checker import batch as B
from comdb2_tpu.checker import linear_jax as LJ
from comdb2_tpu.checker import pallas_seg as PSEG


# --- plan_stream_slices ------------------------------------------------


def test_slices_cover_batch_in_order_no_devices():
    plan = PSEG.plan_stream_slices(10, 0, max_stream_b=4)
    assert plan == [(0, 4, 0), (4, 8, 0), (8, 10, 0)]


def test_slices_spread_across_devices_round_robin():
    # 17 histories over 8 fake devices: group = ceil(17/8) = 3
    plan = PSEG.plan_stream_slices(17, 8, max_stream_b=64)
    assert [s[:2] for s in plan] == [(0, 3), (3, 6), (6, 9), (9, 12),
                                     (12, 15), (15, 17)]
    assert [s[2] for s in plan] == [0, 1, 2, 3, 4, 5]
    # every history appears exactly once, in order
    covered = [i for s, e, _ in plan for i in range(s, e)]
    assert covered == list(range(17))


def test_slices_respect_vmem_cap_even_with_devices():
    # huge batch over 2 devices: slices never exceed the VMEM cap and
    # wrap around the devices
    plan = PSEG.plan_stream_slices(100, 2, max_stream_b=16)
    assert all(e - s <= 16 for s, e, _ in plan)
    assert [d for _, _, d in plan] == [0, 1, 0, 1, 0, 1, 0]
    covered = [i for s, e, _ in plan for i in range(s, e)]
    assert covered == list(range(100))


def test_slices_default_cap_is_kernel_bound():
    plan = PSEG.plan_stream_slices(PSEG.MAX_STREAM_B * 2 + 1, 0)
    assert all(e - s <= PSEG.MAX_STREAM_B for s, e, _ in plan)


def test_single_device_list_still_slices_whole_batch():
    # devices=[one device] (the mesh-of-1 case): same coverage
    plan = PSEG.plan_stream_slices(5, 1, max_stream_b=4)
    covered = [i for s, e, _ in plan for i in range(s, e)]
    assert covered == list(range(5))
    assert all(d == 0 for _, _, d in plan)


# --- merge_stream_slice ------------------------------------------------


def test_merge_converts_global_fail_segments_to_local():
    # the kernel reports fail segments in slice-global coordinates;
    # history 1 starts at segment 7 and failed at global segment 9
    res = np.array([[0, -1, 3],       # valid, 3 final configs
                    [1, 9, 0],        # invalid at global seg 9
                    [2, -1, 0]],      # unknown (overflow)
                   np.int32)
    starts = np.array([0, 7, 12], np.int64)
    out = PSEG.merge_stream_slice(res, starts, 3)
    assert out == [(0, -1, 3), (1, 2, 0), (2, -1, 0)]


def test_merge_handles_partial_slice():
    # the results buffer is padded; only the first n rows are real
    res = np.array([[0, -1, 1], [0, -1, 2], [99, 99, 99]], np.int32)
    starts = np.array([0, 4, 0], np.int64)
    assert PSEG.merge_stream_slice(res, starts, 2) == [(0, -1, 1),
                                                       (0, -1, 2)]


def test_plan_plus_merge_reassembles_solo_order():
    """The invariant the multi-device path must keep: slicing a batch
    over N fake devices and concatenating per-slice merges yields
    exactly the solo-path verdict list."""
    rng = np.random.default_rng(0)
    B_n = 23
    solo = [(int(rng.integers(0, 3)), int(rng.integers(-1, 5)),
             int(rng.integers(0, 9))) for _ in range(B_n)]
    for n_dev in (0, 1, 3, 8):
        plan = PSEG.plan_stream_slices(B_n, n_dev, max_stream_b=4)
        merged = []
        for s, e, _ in plan:
            # fake the kernel's result buffer for this slice: global
            # fail coords = local + a fake per-history segment start
            starts = np.arange(e - s, dtype=np.int64) * 10
            res = np.zeros((e - s, 3), np.int32)
            for i, b in enumerate(range(s, e)):
                st, fl, nf = solo[b]
                res[i] = (st, fl + starts[i] if fl >= 0 else -1, nf)
            merged.extend(PSEG.merge_stream_slice(res, starts, e - s))
        assert merged == solo, f"n_dev={n_dev}"


# --- escalation --------------------------------------------------------


def test_escalation_only_when_budget_exceeds_kernel():
    status = np.array([0, 2, 1, 2], np.int32)
    assert B.escalation_indices(status, F=128, kernel_f=128).size == 0
    idx = B.escalation_indices(status, F=1024, kernel_f=128)
    assert idx.tolist() == [1, 3]


def test_merge_escalation_folds_subbatch_back():
    status = np.array([0, 2, 1, 2], np.int32)
    fail_at = np.array([-1, -1, 5, -1], np.int64)
    n_final = np.array([3, 0, 0, 0], np.int32)
    idx = np.array([1, 3])
    st, fa, nf = B.merge_escalation(
        status, fail_at, n_final, idx,
        np.array([0, 1], np.int32), np.array([-1, 9], np.int64),
        np.array([7, 0], np.int32))
    assert st.tolist() == [0, 0, 1, 1]
    assert fa.tolist() == [-1, -1, 5, 9]
    assert nf.tolist() == [3, 7, 0, 0]
    # inputs are not mutated (pure)
    assert status.tolist() == [0, 2, 1, 2]


def test_f_escalation_wiring_with_fake_stream_engine(monkeypatch):
    """The escalation WIRING in check_batch's stream path, exercised
    on CPU by faking the per-slice dispatch (the real kernel doesn't
    lower here): the fake reports UNKNOWN for one history, and
    check_batch must route exactly that history through the real XLA
    engines at the caller's F and fold the resolved verdict back —
    final results equal solo."""
    import random

    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.synth import register_history

    rng = random.Random(3)
    hs = [register_history(rng, n_procs=3, n_events=40, values=3,
                           p_info=0.0) for _ in range(4)]
    solo = [B.check_batch(B.pack_batch([h], cas_register()), F=1024)
            for h in hs]
    assert all(int(s[0][0]) == 0 for s in solo)   # all genuinely valid

    batch = B.pack_batch(hs, cas_register())

    def fake_dispatch(succ, segs_list, spec, n_states, n_transitions,
                      device=None):
        # history 2 "overflows the kernel frontier"; others check out
        # (4 histories = one pipeline slice, so slice-local indices
        # are batch indices)
        res = np.zeros((len(segs_list), PSEG.LANES), np.int32)
        for i in range(len(segs_list)):
            if i == 2:
                res[i, :3] = (LJ.UNKNOWN, -1, 0)
            else:
                res[i, :3] = (int(solo[i][0][0]), -1,
                              int(solo[i][2][0]))
        return res, np.zeros(len(segs_list), np.int64)

    # the kernel's top rung shrunk to 128: F=1024 then lies above it
    monkeypatch.setattr(PSEG, "F_TOP", PSEG.F)
    monkeypatch.setattr(PSEG, "available", lambda: True)
    monkeypatch.setattr(PSEG, "stream_dispatch", fake_dispatch)

    info: dict = {}
    status, fail_at, n_final = B.check_batch(batch, F=1024, info=info)
    assert info["engine"] == "stream"
    # the UNKNOWN resolved through the ladder; everything matches solo
    for b in range(len(hs)):
        assert int(status[b]) == int(solo[b][0][0]), (b, status)
    assert info.get("escalated", {}).get("count") == 1, info
    assert info["escalated"]["engine"] == "keys"

    # at F == the top rung there is nothing to escalate: the UNKNOWN
    # must surface as-is (re-running at the same budget could only
    # reproduce the overflow)
    info2: dict = {}
    status2, _, _ = B.check_batch(batch, F=PSEG.F, info=info2)
    assert int(status2[2]) == LJ.UNKNOWN
    assert "escalated" not in info2
