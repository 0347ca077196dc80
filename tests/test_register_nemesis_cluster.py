"""The reference's headline test, end to end: ``register-test-nemesis``
is the ONE active deftest in the vendored suite
(``jepsen/test/comdb2/core_test.clj:38-39`` — assert
``(:valid? (:results (jepsen/run! ...)))``), run by ``jepsenloop.sh``
forever on a healed cluster. This is its full in-tree analog:

  provision (SutNodeDB) → 5-node replicated cluster → register workload
  at concurrency 10 ([w cas cas r], core.clj:567-613) with the
  master+1 breaknet nemesis cycling → history → independent-keyed
  linearizable check on the DEVICE engines → perf/timeline artifacts —
  and the verdict must be VALID.
"""

import os
import socket

import pytest

from comdb2_tpu.control.remote import LocalRemote
from comdb2_tpu.harness import core
from comdb2_tpu.harness import generator as G
from comdb2_tpu.harness.provision import SutNodeDB, local_layout
from comdb2_tpu.workloads import comdb2 as W
from comdb2_tpu.workloads.tcp import (ClusterControl,
                                      ClusterPartitioner,
                                      TcpClusterRegisterClient)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BINARY = os.path.join(ROOT, "native", "build", "sut_node")

pytestmark = pytest.mark.skipif(not os.path.exists(BINARY),
                                reason="sut_node not built")


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def test_register_tester_nemesis_end_to_end(tmp_path):
    nodes = ["m1", "m2", "m3", "m4", "m5"]     # the reference's fleet
    ports = _free_ports(5)
    db = SutNodeDB(LocalRemote(), BINARY, local_layout(nodes, ports),
                   base_dir=str(tmp_path / "sut"), timeout_ms=300,
                   elect_ms=500, lease_ms=300)
    ctl = ClusterControl(ports)
    # the linearizable verdict comes from the DEVICE engine (round-4
    # Weak #5: this loop had only ever ended in a host verdict). The
    # per-run shape variance that used to force host — process width
    # moves with partition-window retirements — is gone: slot renaming
    # (LJ.remap_slots) caps the slot axis at max concurrent open
    # calls, and the driver's pow2/even shape buckets bound the
    # remaining compile variety (persistent-cached across runs).
    from comdb2_tpu.checker import checkers as C
    from comdb2_tpu.checker import independent as I
    from comdb2_tpu.report import Timeline, perf_checker

    checker = C.compose({
        "perf": perf_checker(),
        "timeline": Timeline(),
        "linearizable": I.checker(C.Linearizable()),
    })
    # the reference cycle is 10 s on / 10 s off over 300 s; compress to
    # two ~1.2 s partition windows in a ~6 s run so CI stays fast while
    # the history still spans faults and failovers
    nemesis_steps = [G.sleep(0.8), {"type": "info", "f": "start"},
                     G.sleep(1.0), {"type": "info", "f": "stop"},
                     G.sleep(0.8), {"type": "info", "f": "start"},
                     G.sleep(1.0), {"type": "info", "f": "stop"}]
    # generous client timeout + retry budget = the reference's
    # ``set max_retries 100000`` (core.clj:92): indeterminate ops stay
    # rare, so the checker's pending set — every :info pends forever —
    # stays searchable (a stingy 0.5 s/3-retry client turned this
    # history into a >4M-config closure)
    t = W.register_tester_nemesis(opts={
        "nodes": nodes,
        "db": db,
        "store-root": str(tmp_path / "store"),
        "client": TcpClusterRegisterClient(ports, timeout_s=1.0,
                                           mutate_retries=8),
        "nemesis": ClusterPartitioner(ctl, isolate_primary=True),
        "checker": checker,
        "generator": G.phases(
            G.nemesis(
                G.seq(nemesis_steps),
                G.time_limit(6.0, G.stagger(0.02, G.clients(
                    G.mix([W.w, W.cas, W.cas, W.r]))))),
            G.log("quiesce"),
            G.sleep(1.0)),
    })
    result = core.run(t)
    ctl.heal()
    res = result["results"]
    assert res["valid?"] is True, res
    assert res["linearizable"]["valid?"] is True, res["linearizable"]
    # the flagship verdict really ended on the device engine
    (key_res,) = res["linearizable"]["results"].values()
    assert key_res.get("backend") == "device", key_res
    assert key_res.get("engine") in ("xla-seg2", "pallas-fused"), key_res
    assert key_res.get("effective_slots", 99) <= 16, key_res
    history = result["history"]
    oks = [op for op in history
           if op.type == "ok" and op.process != "nemesis"]
    infos = [op for op in history
             if op.type == "info" and op.process != "nemesis"]
    # the run must have real throughput AND really have been hurt by
    # the partitions (indeterminate ops / retired processes), like the
    # reference's nemesis runs
    assert len(oks) >= 80, len(oks)
    starts = [op for op in history
              if op.process == "nemesis" and op.f == "start"]
    assert len(starts) >= 2, "nemesis never fired"
    # perf/timeline artifacts rendered alongside the verdict
    assert res["perf"]["valid?"] is True
    assert res["timeline"]["valid?"] is True

    # the PRODUCTION kernel agrees: re-check the flagship history
    # through the fused Pallas kernel in interpret mode at a FIXED
    # padded spec — segments to a pow2 bucket, K to 8, slots to 14,
    # the successor table to (8, 48) — so the compiled program is
    # byte-identical across runs regardless of history variance (the
    # interpret compile is paid once ever, then rides the persistent
    # cache). Fault-window closures can legitimately exceed 128; the
    # kernel's wide rungs then take the segment, up to F_TOP (past it
    # the production driver escalates to the XLA ladder — the primary
    # verdict above), so the parity contract is the fuzz one: kernel
    # vs the XLA engine AT THE SAME CAPACITY (128, or F_TOP where 128
    # overflows), bit-identical status + fail segment (+ count when
    # VALID). Skipped only when a fault window packed more than 8
    # invokes into one segment (the kernel's K bound).
    import numpy as np

    from comdb2_tpu.checker import independent as I2
    from comdb2_tpu.checker import linear_jax as LJ
    from comdb2_tpu.checker import pallas_seg as PSEG
    from comdb2_tpu.models import model as M
    from comdb2_tpu.models.memo import memo as make_memo
    from comdb2_tpu.ops.packed import pack_history

    sub = I2.subhistory(1, history)     # client values are KVTuples
    packed = pack_history([op for op in sub if op.process != "nemesis"])
    mm = make_memo(M.cas_register(), packed)
    segs = LJ.make_segments(packed)
    from comdb2_tpu.utils import next_pow2
    K_real = segs.inv_proc.shape[1]
    S_real = segs.ok_proc.shape[0]
    # S cap 1024, not the kernel's 2048: the cross-check pads to pow2
    # buckets so the interpret compile is paid once per bucket, and
    # the 512/1024 buckets compile in ~30 s — but the 2048-bucket
    # interpret program measured >17 CPU-MINUTES and ~14.5 GB RSS to
    # compile (the LLVM blowup regime), which can never fit the tier-1
    # budget. A >1024-ok single-key history only happens on an idle
    # machine's fastest runs; those skip the cross-check exactly like
    # the K>8 fault-window case (the primary device verdict above
    # still covers them).
    runnable = K_real <= 8 and S_real <= 1024
    print(f"[flagship] kernel cross-check: K={K_real} S={S_real} "
          f"{'RUN' if runnable else 'SKIP (over kernel bounds)'}")
    if runnable:
        segs = LJ.make_segments(packed,
                                s_pad=next_pow2(S_real, 512), k_pad=8)
        segs, P_eff2 = LJ.remap_slots(segs)
        assert P_eff2 <= 14, P_eff2
        assert mm.n_states <= 8 and mm.n_transitions <= 48, (
            mm.n_states, mm.n_transitions)
        succ_pad = np.full((8, 48), -1, np.int32)
        succ_pad[:mm.n_states, :mm.n_transitions] = mm.succ
        PSEG.use_interpret(True)
        try:
            r = PSEG.check_device_pallas(succ_pad, segs, n_states=8,
                                         n_transitions=48, P=14)
        finally:
            PSEG.use_interpret(False)
        assert r is not None, "fixed spec must be kernel-eligible"
        x = LJ.check_device_seg2(
            LJ.pad_succ(succ_pad, 8, 64), segs.inv_proc, segs.inv_tr,
            segs.ok_proc, segs.depth, F=128, Fs=32, P=14,
            n_states=8, n_transitions=48)
        x = tuple(int(v) for v in x)
        print(f"[flagship] kernel={r} xla@128={x}")
        if x[0] == LJ.UNKNOWN:
            x = tuple(int(v) for v in LJ.check_device_seg2(
                LJ.pad_succ(succ_pad, 8, 64), segs.inv_proc,
                segs.inv_tr, segs.ok_proc, segs.depth, F=PSEG.F_TOP,
                Fs=32, P=14, n_states=8, n_transitions=48))
            print(f"[flagship] xla@{PSEG.F_TOP}={x}")
        assert r[0] == x[0], (r, x)
        assert r[1] == x[1], (r, x)            # same fail segment
        if r[0] == LJ.VALID:
            assert r[2] == x[2], (r, x)
        else:
            # overflow is legitimate under fault windows, but the
            # HISTORY itself is linearizable — the primary device
            # verdict at the escalated capacity said so above
            assert r[0] == LJ.UNKNOWN, r
