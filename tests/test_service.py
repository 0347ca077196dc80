"""The verifier service: shape bucketing, request coalescing,
deadlines/backpressure/degradation, the TCP daemon end to end, and
the store artifact of service runs.

The core tests drive :class:`VerifierCore` in-process (the daemon is
a thin selector loop over it); one test boots the real daemon
subprocess and exercises the wire path including a client disconnect
mid-request and a clean shutdown."""

import json
import os
import random
import socket
import subprocess
import sys
import time

import pytest

from comdb2_tpu.checker import linear
from comdb2_tpu.models import model as M
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.history import history_to_edn
from comdb2_tpu.ops.packed import pack_history
from comdb2_tpu.ops.synth import register_history
from comdb2_tpu.service import ServiceLimits, VerifierCore, bucket_for

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _core(**kw):
    kw.setdefault("F", 64)
    kw.setdefault("batch_cap", 8)
    return VerifierCore(**kw)


def _submit(core, h, **fields):
    return core.submit({"op": "check",
                        "history": history_to_edn(list(h)),
                        **fields}, time.monotonic())


INVALID = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
           O.invoke(1, "read", None), O.Op(1, "ok", "read", 2)]


# --- bucketing ---------------------------------------------------------------

def test_bucket_axes_quantized():
    h = register_history(random.Random(0), 3, 40, p_info=0.0)
    b = bucket_for(pack_history(list(h)), ServiceLimits())
    for axis in (b.n_pad, b.S, b.K, b.P):
        assert axis & (axis - 1) == 0, b   # pow2 quantization
    # effective slots: even-bucketed inside the kernel's (8,128) tier
    assert b.P_eff % 2 == 0 or b.P_eff > 7
    assert b.key == \
        f"n{b.n_pad}-s{b.S}-k{b.K}-p{b.P}-e{b.P_eff}"
    # the admission pass caches the exact segment stream for dispatch
    packed = pack_history(list(h))
    bucket_for(packed, ServiceLimits())
    assert getattr(packed, "_segments_exact", None) is not None


def test_bucket_rejects_over_limits():
    # 9 concurrent pending invokes before the first ok: K=9 exceeds
    # the kernel-derived cap -> host route
    wide = [O.invoke(i, "write", i) for i in range(9)]
    wide += [O.ok(i, "write", i) for i in range(9)]
    assert bucket_for(pack_history(list(wide)),
                      ServiceLimits()) is None
    # and a bucketed history stays bucketed
    h = register_history(random.Random(1), 3, 24, p_info=0.0)
    assert bucket_for(pack_history(list(h)),
                      ServiceLimits()) is not None


# --- coalescing + shared programs --------------------------------------------

def test_mixed_sizes_coalesce_and_share_programs():
    """Different raw sizes landing in one bucket ride ONE dispatch,
    and a later same-shape tick reuses the compiled program."""
    core = _core()
    # same generator params, different seeds: same bucket by
    # construction of the quantization (sizes differ only sub-pow2)
    pairs = [(11, 12), (13, 14)]
    keys = set()
    for seed_a, seed_b in pairs:
        ha = register_history(random.Random(seed_a), 3, 40, p_info=0.0)
        hb = register_history(random.Random(seed_b), 3, 40, p_info=0.0)
        ba = bucket_for(pack_history(list(ha)), core.limits)
        bb = bucket_for(pack_history(list(hb)), core.limits)
        if ba != bb:
            continue                      # seed landed a different K
        keys.add(ba.key)
        p1, r1 = _submit(core, ha)
        p2, r2 = _submit(core, hb)
        assert r1 is None and r2 is None  # queued, not immediate
        done = core.tick()
        assert len(done) == 2
        for _, reply in done:
            assert reply["valid"] is True
            assert reply["batched"] == 2
            assert reply["bucket"] == ba.key
    assert keys, "no seed pair shared a bucket — fixture broke"
    st = core.status()
    for key in keys:
        bs = st["buckets"][key]
        # both ticks of a shared bucket ran the same program: one
        # compile, then hits
        assert bs["dispatches"] >= 1
        assert bs["compiles"] <= 1 or bs["dispatches"] == bs["compiles"]
    if len(keys) == 1 and st["buckets"][next(iter(keys))][
            "dispatches"] == 2:
        assert st["program_hits"] >= 1


def test_verdict_matches_host_oracle():
    core = _core()
    exp = linear.analysis(M.cas_register(), list(INVALID),
                          backend="host")
    assert exp.valid is False
    _submit(core, INVALID)
    ((_, reply),) = core.tick()
    assert reply["valid"] is False
    assert reply["op_index"] == exp.op_index


# --- deadlines, backpressure, degradation ------------------------------------

def test_deadline_expired_answers_unknown_without_blocking():
    core = _core()
    h = register_history(random.Random(2), 3, 24, p_info=0.0)
    _submit(core, h, deadline_ms=0)       # expired on arrival
    _submit(core, h)
    time.sleep(0.002)
    done = core.tick()
    by_valid = {}
    for _, reply in done:
        by_valid.setdefault(str(reply["valid"]), reply)
    assert by_valid["unknown"]["cause"] == "deadline"
    assert by_valid["True"]["batched"] == 1   # batch ran without it
    assert core.m["deadline_expired"] == 1


def test_overload_is_explicit_and_immediate():
    core = _core(max_queue=2)
    h = register_history(random.Random(3), 3, 24, p_info=0.0)
    assert _submit(core, h)[1] is None
    assert _submit(core, h)[1] is None
    _, reply = _submit(core, h)
    assert reply["ok"] is False and reply["error"] == "overload"
    # the backoff hint: derived from queue depth + drain rate,
    # clamped to [25 ms, 5 s]
    assert 25 <= reply["retry_after_ms"] <= 5000
    assert core.m["overloads"] == 1
    core.tick()                            # queued two still answer


def test_over_k_history_degrades_to_host_with_same_verdict():
    core = _core()
    wide = [O.invoke(i, "write", i) for i in range(9)]
    wide += [O.ok(i, "write", i) for i in range(9)]
    exp = linear.analysis(M.cas_register(), list(wide), backend="host")
    pending, reply = _submit(core, wide)
    assert reply is None and pending.bucket is None
    ((_, reply),) = core.tick()
    assert reply["engine"] == "host" and reply["degraded"]
    assert reply["valid"] == exp.valid
    assert core.m["host_degraded"] == 1


def test_malformed_and_trivial_histories_answer_immediately():
    core = _core()
    # double-pending process WITH a completion: malformed -> unknown
    mal = [O.invoke(0, "write", 1), O.invoke(0, "write", 2),
           O.ok(0, "write", 1)]
    _, reply = _submit(core, mal)
    assert reply["valid"] == "unknown"
    assert "malformed" in reply["cause"]
    # no ok-completions: nothing constrains the frontier
    _, reply = _submit(core, [O.invoke(0, "write", 1)])
    assert reply["valid"] is True and reply["engine"] == "trivial"
    # garbage text: bad-request, not an exception
    _, reply = core.submit({"op": "check", "history": "]not edn["},
                           time.monotonic())
    assert reply["ok"] is False and reply["error"] == "bad-request"
    assert not core.queue


def test_prime_warms_programs_for_matching_traffic():
    core = _core()
    n = core.prime(specs=((24, 2),), seed=41)
    assert n >= 1
    st = core.status()
    assert st["primed"] == n and st["compiles"] >= 1
    assert st["completed"] == 0            # priming isn't traffic
    # identical-shape traffic (same generator, same seed) hits the
    # primed program instead of compiling
    h = register_history(random.Random(41), 3, 24, p_info=0.0)
    _submit(core, h)
    _submit(core, h)
    core.tick()
    st2 = core.status()
    assert st2["compiles"] == st["compiles"]
    assert st2["program_hits"] >= 1


# --- the wire ----------------------------------------------------------------

def _spawn_daemon(*extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "comdb2_tpu.service", "--port", "0",
         "--backend", "cpu", "--no-prime", "--frontier", "64",
         "--coalesce-ms", "2", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=ROOT, env=env)
    ready = json.loads(proc.stdout.readline())
    assert ready.get("ready"), ready
    return proc, ready["port"]


def test_daemon_peer_closed_before_reply():
    """A reply due to a client that already closed its end counts as
    dropped: the first write to such a socket raises nothing (TCP
    answers it with a reset), so the daemon looks for the EOF first.
    A live client, idle or with pipelined requests unread, is not
    taken for gone."""
    from comdb2_tpu.service.daemon import VerifierDaemon

    gone, peer = socket.socketpair()
    live, live_peer = socket.socketpair()
    busy, busy_peer = socket.socketpair()
    try:
        for s in (gone, live, busy):
            s.setblocking(False)
        peer.close()
        busy_peer.sendall(b'{"op": "ping"}\n')
        assert VerifierDaemon._peer_closed(gone)
        assert not VerifierDaemon._peer_closed(live)
        assert not VerifierDaemon._peer_closed(busy)
        assert busy.recv(64) == b'{"op": "ping"}\n'   # peek kept it
    finally:
        for s in (gone, live, live_peer, busy, busy_peer):
            s.close()


def test_daemon_end_to_end(tmp_path):
    from comdb2_tpu.service.client import ServiceClient, ServiceError

    proc, port = _spawn_daemon()
    try:
        c = ServiceClient("127.0.0.1", port, timeout_s=300.0)
        h = register_history(random.Random(5), 3, 40, p_info=0.0)
        r = c.check(h)
        assert r["ok"] and r["valid"] is True
        r = c.check(INVALID)
        assert r["valid"] is False and r["op_index"] == 3
        # unknown model -> ServiceError, daemon alive
        with pytest.raises(ServiceError):
            c.check(h, model="no-such-model")
        # disconnect mid-request: reply dropped, daemon keeps serving
        s = socket.create_connection(("127.0.0.1", port))
        s.sendall((json.dumps(
            {"op": "check", "history": history_to_edn(h)}) +
            "\n").encode())
        s.close()
        # the drop is counted when the reply is due, after a check a
        # loaded machine may take tens of seconds to run
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            st = c.status()["status"]
            if st["dropped_replies"] >= 1:
                break
            time.sleep(0.05)
        assert st["dropped_replies"] >= 1
        assert c.ping()
        assert c.check(h)["valid"] is True
        st = c.status()["status"]
        assert st["accepted"] >= 4 and st["dispatches"] >= 3
        assert st["latency_ms"]["p50"] > 0
        # filetest --service round-trips the same daemon
        edn = tmp_path / "hist.edn"
        edn.write_text(history_to_edn(h))
        r = subprocess.run(
            [sys.executable, "-m", "comdb2_tpu.filetest", str(edn),
             "--service", f"127.0.0.1:{port}"],
            cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "'valid': True" in r.stdout
        assert c.shutdown()
    except BaseException:
        # kill at once, so the failure above is what the run reports
        proc.kill()
        proc.wait(timeout=30)
        raise
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()               # never leak a daemon into the suite
        proc.wait(timeout=30)
        raise
    assert rc == 0


# --- store artifact ----------------------------------------------------------

def test_store_service_status_artifact(tmp_path):
    from comdb2_tpu.harness.store import save_service_status

    core = _core()
    p = save_service_status(core.status(), store_root=str(tmp_path))
    p = save_service_status(core.status(), store_root=str(tmp_path))
    with open(p) as fh:
        latest = json.loads(fh.read())
    assert latest["queue_depth"] == 0
    with open(os.path.join(str(tmp_path), "service",
                           "status.jsonl")) as fh:
        assert len(fh.readlines()) == 2


# --- the parallel shim -------------------------------------------------------

def test_parallel_shim_reexports_sharding():
    import comdb2_tpu.parallel as shim
    from comdb2_tpu.service import sharding

    assert shim.make_mesh is sharding.make_mesh
    assert shim.check_histories_sharded is \
        sharding.check_histories_sharded


# --- the txn (serializability) request kind ---------------------------------
#
# Same queue, same tick, same overload/deadline answers as the check
# kind; the device work is the matrix-closure engine, coalesced per
# pow2 txn-count bucket.

from comdb2_tpu.ops.synth import (list_append_history,
                                  txn_anomaly_history)
from comdb2_tpu.service.bucketing import TxnBucket, txn_bucket_for


def _submit_txn(core, h, **fields):
    return core.submit({"op": "check", "kind": "txn",
                        "history": history_to_edn(list(h)),
                        **fields}, time.monotonic())


def test_txn_bucket_quantized_and_limited():
    limits = ServiceLimits()
    assert txn_bucket_for(3, limits) == TxnBucket(N=16)
    assert txn_bucket_for(17, limits) == TxnBucket(N=32)
    assert txn_bucket_for(limits.max_txns + 1, limits) is None
    assert TxnBucket(N=64).key == "txn-n64"


def test_txn_requests_coalesce_and_classify():
    core = _core()
    p1, r1 = _submit_txn(core, txn_anomaly_history("g2-item"))
    p2, r2 = _submit_txn(core, list_append_history(
        random.Random(3), 3, 10, 2))
    assert r1 is None and r2 is None
    assert p1.bucket == p2.bucket == TxnBucket(N=16)
    done = core.tick()
    assert len(done) == 2
    bad = next(r for _, r in done if r["valid"] is False)
    good = next(r for _, r in done if r["valid"] is True)
    assert bad["anomaly_class"] == "G2-item"
    assert bad["batched"] == 2 and bad["engine"] == "closure"
    assert [s["edge"]["type"] for s in bad["cycle"]] == ["rw", "rw"]
    assert good["kind"] == "txn"
    st = core.status()
    assert st["buckets"]["txn-n16"]["dispatches"] == 1
    assert st["buckets"]["txn-n16"]["batched"] == 2


def test_txn_and_check_kinds_share_one_tick():
    core = _core()
    _submit(core, register_history(random.Random(0), 3, 24,
                                   p_info=0.0))
    _submit_txn(core, txn_anomaly_history("clean"))
    done = core.tick()
    kinds = sorted(r.get("kind", "check") for _, r in done)
    assert kinds == ["check", "txn"]


def test_txn_program_reuse_across_ticks():
    core = _core()
    for seed in (1, 2):
        _submit_txn(core, list_append_history(
            random.Random(seed), 3, 10, 2))
        done = core.tick()
        assert done[-1][1]["valid"] is True
    bs = core.status()["buckets"]["txn-n16"]
    assert bs["dispatches"] == 2 and bs["compiles"] == 1
    assert core.m["program_hits"] >= 1


def test_txn_deadline_parity():
    core = _core()
    _submit_txn(core, txn_anomaly_history("g2-item"), deadline_ms=0)
    time.sleep(0.002)
    ((_, reply),) = core.tick()
    assert reply["valid"] == "unknown" and reply["cause"] == "deadline"
    assert core.m["deadline_expired"] == 1
    _, bad = _submit_txn(core, txn_anomaly_history("g2-item"),
                         deadline_ms="soon")
    assert bad == {"ok": False, "error": "bad-request",
                   "message": bad["message"]}


def test_txn_overload_parity():
    core = _core(max_queue=1)
    assert _submit_txn(core, txn_anomaly_history("g2-item"))[1] is None
    _, reply = _submit_txn(core, txn_anomaly_history("g2-item"))
    assert reply["ok"] is False and reply["error"] == "overload"
    assert 25 <= reply["retry_after_ms"] <= 5000
    assert core.m["overloads"] == 1
    # and a check-kind request sheds identically at the shared cap
    _, reply = _submit(core, register_history(random.Random(1), 3, 24,
                                              p_info=0.0))
    assert reply["error"] == "overload"


def test_txn_trivial_and_direct_anomalies_answer_immediately():
    core = _core()
    # edge-free but anomalous: a doubled value nobody ever appended
    # leaves no edges, so no cycle engine runs — yet the verdict is
    # already decided at admission
    h = [O.invoke(0, "txn", (("r", 0, None),)),
         O.Op(0, "ok", "txn", (("r", 0, (1, 1)),))]
    _, reply = _submit_txn(core, h)
    assert reply is not None and reply["valid"] is False
    assert "duplicate" in reply["anomalies"]
    assert reply["engine"] == "trivial"
    # edge-free and clean: immediate valid
    h = [O.invoke(0, "txn", (("append", 0, 1),)),
         O.Op(0, "ok", "txn", (("append", 0, 1),))]
    _, reply = _submit_txn(core, h)
    assert reply is not None and reply["valid"] is True


def test_txn_over_limit_degrades_to_host_scc():
    core = _core(limits=ServiceLimits(max_txns=2))
    p, reply = _submit_txn(core, txn_anomaly_history("g2-item"))
    assert reply is None and p.bucket is None
    ((_, reply),) = core.tick()
    assert reply["engine"] == "host" and reply["degraded"]
    assert reply["valid"] is False
    assert reply["anomaly_class"] == "G2-item"
    assert core.m["host_degraded"] == 1


def test_txn_malformed_answers_unknown_or_bad_request():
    core = _core()
    # double-pending process: malformed -> unknown (same contract as
    # the check kind's pack failures)
    h = [O.invoke(0, "txn", (("append", 0, 1),)),
         O.invoke(0, "txn", (("append", 0, 2),))]
    _, reply = _submit_txn(core, h)
    assert reply["valid"] == "unknown"
    assert "malformed" in reply["cause"]
    # garbage EDN -> bad-request
    _, reply = core.submit({"op": "check", "kind": "txn",
                            "history": "{:not-an-op"},
                           time.monotonic())
    assert reply["error"] == "bad-request"


def test_txn_realtime_flag_strictens():
    # serializable but NOT strictly so: t1's read is STALE — it ran
    # wholly after t0's append committed yet observed nothing, so the
    # only valid serialization (t1 before t0) contradicts realtime
    h = [O.invoke(0, "txn", (("append", 0, 7),)),
         O.Op(0, "ok", "txn", (("append", 0, 7),)),
         O.invoke(1, "txn", (("r", 0, None),)),
         O.Op(1, "ok", "txn", (("r", 0, ()),)),
         O.invoke(2, "txn", (("r", 0, None),)),
         O.Op(2, "ok", "txn", (("r", 0, (7,)),))]
    core = _core()
    p, r = _submit_txn(core, h)
    if r is None:
        ((_, r),) = core.tick()
    assert r["valid"] is True, r
    p, r2 = _submit_txn(core, h, realtime=True)
    if r2 is None:
        ((_, r2),) = core.tick()
    assert r2["valid"] is False, r2     # rw against realtime order


def test_txn_partially_malformed_answers_unknown_from_batch():
    """A history WITH edges plus one unparseable micro-op must answer
    unknown from the coalesced dispatch path — identical to what
    check_txn answers on every other surface (review regression)."""
    h = list(txn_anomaly_history("clean"))
    h += [O.invoke(9, "txn", (("x", 0, 1),)),
          O.Op(9, "ok", "txn", (("x", 0, 1),))]
    core = _core()
    p, r = _submit_txn(core, h)
    assert r is None                     # queued: the graph has edges
    ((_, reply),) = core.tick()
    assert reply["valid"] == "unknown", reply
    assert reply["malformed_ops"] == 1
    assert "malformed" in reply["cause"]
    from comdb2_tpu.txn import check_txn
    assert check_txn(h, backend="host")["valid?"] == "unknown"


def test_txn_deadline_reply_carries_kind():
    core = _core()
    _submit_txn(core, txn_anomaly_history("g2-item"), deadline_ms=0)
    time.sleep(0.002)
    ((_, reply),) = core.tick()
    assert reply["kind"] == "txn" and reply["cause"] == "deadline"
