"""Continuous-batching admission (round 9): slot-filling launches
(full / deadline-budget / idle), per-bucket fairness under a
hot-bucket flood, the bounded in-flight ring with mixed request
kinds, donated-carry bit-parity (stream kernel and mesh-sharded
closure), overload retry_after_ms + jittered client backoff, and the
consistent-hash routing layer (ring math + failover)."""

import random
import time

import numpy as np
import pytest

from comdb2_tpu.obs import trace as obs
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.history import history_to_edn
from comdb2_tpu.ops.synth import register_history, txn_anomaly_history
from comdb2_tpu.service import VerifierCore


def _core(**kw):
    kw.setdefault("F", 64)
    kw.setdefault("batch_cap", 8)
    return VerifierCore(**kw)


def _submit(core, h, now=None, **fields):
    return core.submit({"op": "check",
                        "history": history_to_edn(list(h)),
                        **fields},
                       obs.monotonic() if now is None else now)


def _histories(seed0, n, n_events=40):
    return [register_history(random.Random(seed0 + i), 3, n_events,
                             p_info=0.0) for i in range(n)]


# --- launch policy -----------------------------------------------------------

def test_full_batch_launches_at_submit():
    """A bucket that reaches the cap dispatches inside submit itself
    — no scheduler beat, no fill window (the slot-filling contract).
    The same history twice guarantees one shared bucket."""
    core = _core(batch_cap=2, fill_window_s=10.0)
    h = _histories(11, 1)[0]
    p1, r1 = _submit(core, h)
    assert core.inflight() == 0 and r1 is None
    p2, r2 = _submit(core, h)
    assert p1.bucket == p2.bucket        # identical text, same bucket
    assert core.m["launch_full"] == 1
    assert core.inflight() == 1          # staged, not yet finalized
    assert core.queue_depth() == 0
    done = core.tick()                   # drain the ring
    assert len(done) == 2
    for _, reply in done:
        assert reply["valid"] is True


def test_deadline_derived_launch_budget():
    """A request's launch budget is deadline-derived: with a huge
    fill window, a 100 ms deadline still launches within ~50 ms
    (half the headroom stays reserved for the dispatch)."""
    core = _core(fill_window_s=10.0)
    t0 = obs.monotonic()
    p, r = _submit(core, _histories(21, 1)[0], now=t0,
                   deadline_ms=100)
    assert r is None
    assert p.t_budget <= t0 + 0.051
    # before the budget: a non-idle pump must NOT launch
    done = core.pump(now=t0 + 0.01)
    assert core.m["launch_deadline"] == 0 and core.queue_depth() == 1
    # after the budget: launched for deadline reasons, then served
    done += core.pump(now=t0 + 0.06)
    assert core.m["launch_deadline"] == 1
    done += core.tick()
    assert len(done) == 1 and done[0][1]["valid"] is True
    assert core.m["deadline_expired"] == 0


def test_hot_bucket_flood_cold_bucket_launches_within_budget():
    """Per-bucket fairness: a flood filling one bucket's batches must
    not hold a cold bucket's lone request past its launch budget."""
    core = _core(batch_cap=4, fill_window_s=0.02)
    hot_h = _histories(31, 1, n_events=40)[0]
    cold = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
            O.invoke(1, "read", None), O.Op(1, "ok", "read", 2)]
    t0 = obs.monotonic()
    pc, _ = _submit(core, cold, now=t0)
    for _ in range(12):                   # 3 full same-bucket batches
        _submit(core, hot_h, now=t0)
    assert core.m["launch_full"] == 3      # the flood launched itself
    # the cold bucket launches once its budget expires — without
    # waiting for the hot bucket to go quiet (reason: deadline, never
    # a whole-queue drain round)
    done = core.pump(now=t0 + 0.021)
    assert core.m["launch_deadline"] >= 1
    done += core.tick()
    cold_reply = next(r for p, r in done if p is pc)
    assert cold_reply["valid"] is False    # the stale-read repro
    assert len(done) == 13


def test_idle_launch_answers_serial_callers():
    core = _core(fill_window_s=10.0)
    _submit(core, _histories(41, 1)[0])
    done = core.pump(idle=True)            # quiet wire -> launch+drain
    assert core.m["launch_idle"] == 1
    assert len(done) == 1 and done[0][1]["valid"] is True


# --- the in-flight ring ------------------------------------------------------

def test_ring_bounds_staged_dispatches():
    """More launchable buckets than ring slots: the ring finalizes
    oldest-first on overflow, every reply still arrives, and the
    occupancy gauge ends at zero. The fill window outlasts the test,
    so every launch is the flush's idle launch, never a deadline one
    however long the submits take."""
    core = _core(batch_cap=8, ring_depth=2, fill_window_s=3600.0)
    sizes = (16, 40, 88, 150)             # 4 distinct shape buckets
    for i, n_events in enumerate(sizes):
        _submit(core, register_history(random.Random(51 + i), 3,
                                       n_events, p_info=0.0))
    done = core.tick()
    assert len(done) == 4
    assert {r["valid"] for _, r in done} == {True}
    assert core.m["dispatches"] >= 3       # distinct buckets staged
    assert core.inflight() == 0
    snap = core.metrics_reply()["metrics"]
    assert snap["service_inflight_ring"]["series"][0]["value"] == 0
    assert snap["service_launch_idle_total"]["series"][0]["value"] \
        >= 1


def test_ring_drains_on_busy_pump_when_nothing_forms():
    """Non-queuing traffic (status/ping polls) keeps the daemon's
    got_bytes true forever — a staged dispatch must still finalize on
    a NON-idle pump once no batch is forming, or its reply defers
    indefinitely (review regression)."""
    core = _core(batch_cap=1, fill_window_s=10.0)
    _submit(core, _histories(45, 1)[0])    # cap 1 -> launches at
    assert core.inflight() == 1            # submit, staged in ring
    done = core.pump(idle=False)           # busy beat, nothing forms
    assert core.inflight() == 0
    assert len(done) == 1 and done[0][1]["valid"] is True


def test_mixed_kinds_interleave_in_ring():
    """check + txn dispatches ride the same ring; a shrink job's
    rounds interleave between them — one pump serves all three
    kinds."""
    core = _core()
    _submit(core, _histories(61, 1)[0])
    core.submit({"op": "check", "kind": "txn",
                 "history": history_to_edn(
                     list(txn_anomaly_history("g2-item")))},
                obs.monotonic())
    bad = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
           O.invoke(1, "read", None), O.Op(1, "ok", "read", 2)]
    core.submit({"op": "check", "kind": "shrink",
                 "history": history_to_edn(bad)}, obs.monotonic())
    deadline = time.monotonic() + 120
    done = []
    while len(done) < 3 and time.monotonic() < deadline:
        done += core.tick()
    kinds = sorted(r.get("kind", "check") for _, r in done)
    assert kinds == ["check", "shrink", "txn"]
    shrink_reply = next(r for _, r in done
                        if r.get("kind") == "shrink")
    assert shrink_reply["valid"] is False
    assert shrink_reply["minimal_ops"] <= 4
    txn_reply = next(r for _, r in done if r.get("kind") == "txn")
    assert txn_reply["anomaly_class"] == "G2-item"


# --- donated carries ---------------------------------------------------------

def test_donated_carry_parity_stream():
    """Bit-parity of the donated stream-kernel path on the
    interpret-mode kernel: donated + pooled (the rerun must HIT the
    carry pool) vs the plain path must agree exactly. The closure
    kernels deliberately do not donate — their packed upload can
    never alias the smaller diagonal output (closure_jax docstring),
    and mesh closure parity is covered by test_mesh_parity."""
    from comdb2_tpu.checker import batch as B
    from comdb2_tpu.checker import pallas_seg as PS
    from comdb2_tpu.models import model as M

    hs = _histories(71, 4, n_events=24)
    model = M.cas_register()
    PS.use_interpret(True)
    try:
        assert PS.donation_active()
        info: dict = {}
        r_don = B.check_batch(B.pack_batch(hs, model), F=64, info=info)
        assert info["engine"] == "stream"
        reuses0 = PS.CARRY_REUSES
        r_don2 = B.check_batch(B.pack_batch(hs, model), F=64)
        assert PS.CARRY_REUSES > reuses0   # the pool served a rerun
        PS.use_carry_donation(False)
        r_plain = B.check_batch(B.pack_batch(hs, model), F=64)
    finally:
        PS.use_carry_donation(True)
        PS.use_interpret(False)
    for a, b in ((r_don, r_plain), (r_don2, r_plain)):
        for x, y in zip(a, b):
            assert (np.asarray(x) == np.asarray(y)).all()


# --- overload backoff --------------------------------------------------------

def test_overload_reply_has_drain_derived_retry_after():
    core = _core(max_queue=2, fill_window_s=0.001)
    hs = _histories(81, 3)
    _submit(core, hs[0])
    core.tick()                            # builds drain history
    _submit(core, hs[0])
    _submit(core, hs[1])
    _, reply = _submit(core, hs[2])
    assert reply["error"] == "overload"
    assert 25 <= reply["retry_after_ms"] <= 5000


def test_client_backs_off_on_overload(monkeypatch):
    """The client honors retry_after_ms with jitter (never a fixed
    interval) and retries the request instead of surfacing the first
    overload."""
    from comdb2_tpu.service.client import ServiceClient

    c = ServiceClient.__new__(ServiceClient)
    c.overload_retries = 2
    c._rng = random.Random(3)
    replies = [{"ok": False, "error": "overload",
                "retry_after_ms": 200},
               {"ok": False, "error": "overload",
                "retry_after_ms": 200},
               {"ok": True, "valid": True}]
    calls = {"n": 0}

    def fake_request(obj):
        out = replies[calls["n"]]
        calls["n"] += 1
        return out

    slept = []
    monkeypatch.setattr(c, "_request", fake_request)
    monkeypatch.setattr("comdb2_tpu.service.client.time.sleep",
                        slept.append)
    out = c._request_shedding({"op": "check"})
    assert out["ok"] is True and calls["n"] == 3
    assert len(slept) == 2
    for s in slept:                        # jittered around the hint
        assert 0.1 <= s <= 0.3
    assert slept[0] != slept[1]            # not a fixed interval


# --- consistent-hash routing -------------------------------------------------

def test_hash_ring_balance_and_minimal_remap():
    from comdb2_tpu.service.client import HashRing

    two = HashRing(["sut/verifier/0", "sut/verifier/1"])
    owners = [two.nodes_for(f"k{i}")[0] for i in range(400)]
    share = owners.count("sut/verifier/0") / 400
    assert 0.3 <= share <= 0.7             # balanced-ish
    # failover chain covers every distinct node, owner first
    chain = two.nodes_for("some-key")
    assert len(chain) == 2 and set(chain) == set(two.nodes)
    # adding a node only moves keys TO the new node
    three = HashRing(["sut/verifier/0", "sut/verifier/1",
                      "sut/verifier/2"])
    for i in range(400):
        a, b = two.nodes_for(f"k{i}")[0], three.nodes_for(f"k{i}")[0]
        assert b == a or b == "sut/verifier/2"


def test_routed_client_shape_affinity_and_failover():
    from comdb2_tpu.service.client import RoutedClient

    class Stub:
        def __init__(self, fail=False):
            self.fail = fail
            self.calls = 0

        def check(self, history, **kw):
            self.calls += 1
            if self.fail:
                raise OSError("down")
            return {"ok": True, "valid": True}

        def close(self):
            pass

    a, b = Stub(), Stub()
    rc = RoutedClient({"sut/verifier/0": a, "sut/verifier/1": b})
    h_small = history_to_edn(_histories(91, 1, n_events=10)[0])
    h_big = history_to_edn(_histories(92, 1, n_events=60)[0])
    # same shape class -> same daemon, every time (program affinity)
    owners = {rc.ring.nodes_for(
        RoutedClient.route_key(h_small))[0] for _ in range(3)}
    assert len(owners) == 1
    for _ in range(3):
        assert rc.check(h_small)["ok"]
        assert rc.check(h_big)["ok"]
    assert a.calls + b.calls == 6
    # kill the owner of h_small: requests fail over, none are lost
    owner = rc.ring.nodes_for(RoutedClient.route_key(h_small))[0]
    rc.clients[owner].fail = True
    assert rc.check(h_small)["ok"]
    assert rc.failovers == 1


def test_routed_discover_requires_registrations():
    from comdb2_tpu.service.client import RoutedClient

    with pytest.raises(ValueError):
        RoutedClient({})
