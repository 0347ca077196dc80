"""The key split of ``independent/checker`` in one pass, and its
spans. The per-key walk (``subhistory`` for each of ``history_keys``,
the form the checker used before) is the oracle: the one-pass split
must give every key the same subhistory, un-keyed ops included in
every key, in order (``independent.clj:240-250``)."""

import random

import pytest

from comdb2_tpu.checker import independent
from comdb2_tpu.checker import pallas_seg as PS
from comdb2_tpu.checker.checkers import Linearizable
from comdb2_tpu.models import model as M
from comdb2_tpu.obs import trace
from comdb2_tpu.ops import op as O
from comdb2_tpu.ops.kv import tuple_


def _keyed_history(seed, n_keys, n_ops, p_unkeyed=0.05):
    rng = random.Random(seed)
    h = [O.info("nemesis", "start", None)]      # before any key
    for i in range(n_ops):
        if rng.random() < p_unkeyed:
            h.append(O.info("nemesis", rng.choice(["start", "stop"]),
                            None))
            continue
        k = rng.randrange(n_keys)
        p = 10 * k + rng.randrange(3)
        v = rng.randrange(4)
        h.append(O.invoke(p, "write", tuple_(k, v)))
        h.append(O.ok(p, "write", tuple_(k, v)))
    return h


def _per_key_walk(history):
    return {k: independent.subhistory(k, history)
            for k in independent.history_keys(history)}


@pytest.mark.parametrize("seed,n_keys", [(1, 1), (2, 3), (3, 8),
                                         (4, 32), (5, 5), (6, 2),
                                         (7, 64), (8, 4), (9, 16),
                                         (10, 7)])
def test_one_pass_split_is_the_per_key_walk(seed, n_keys):
    h = _keyed_history(seed, n_keys, 200)
    got = independent.split(h)
    want = _per_key_walk(h)
    assert list(got) == list(want)            # first-appearance order
    assert got == want
    n_unkeyed = sum(1 for op in h if op.f in ("start", "stop"))
    for sub in got.values():
        assert sum(1 for op in sub if op.f in ("start", "stop")) \
            == n_unkeyed


def test_split_of_a_history_without_keys_is_empty():
    h = [O.invoke(0, "write", 1), O.ok(0, "write", 1)]
    assert independent.split(h) == {} == _per_key_walk(h)


@pytest.fixture()
def traced():
    trace.clear()
    trace.enable()
    yield
    trace.disable()
    trace.clear()


def _rung_history():
    """Key 0: seven writes pending together (a closure of 449 configs:
    the kernel's second wide rung); key 1: a read of a value never
    written (invalid)."""
    h = [O.invoke(p, "write", tuple_(0, p)) for p in range(7)]
    h += [O.ok(p, "write", tuple_(0, p)) for p in range(7)]
    h += [O.invoke(20, "write", tuple_(1, 1)), O.ok(20, "write", tuple_(1, 1)),
          O.invoke(21, "read", tuple_(1, None)),
          O.ok(21, "read", tuple_(1, 3))]
    return h


def test_split_and_recheck_spans_on_a_traced_check(traced):
    h = _rung_history()
    r = independent.checker(Linearizable()).check({}, M.cas_register(), h)
    assert r["valid?"] is False and r["failures"] == [1]
    spans = trace.spans()
    split = [s for s in spans if s.name == "independent.split"]
    assert len(split) == 1
    assert split[0].args == {"keys": 2, "events": len(h)}
    # on the CPU the batch runs the XLA keys engine at its budget of
    # 256 configs: key 0's closure overflows it and is checked again
    # for that reason, key 1 for its counterexample
    rechecks = [s.args for s in spans if s.name == "independent.recheck"]
    assert rechecks == [{"key": "0", "reason": "overflow"},
                        {"key": "1", "reason": "invalid"}]
    assert r["results"][0]["valid?"] is True


def test_rung_counters_ride_the_collect_span(traced):
    """Through the stream kernel (interpret mode): the key whose
    closure passes 128 ran segments on a wide rung, and the counters
    came back on ``batch.collect`` with the verdicts."""
    PS.use_interpret(True)
    try:
        r = independent.checker(Linearizable()).check(
            {}, M.cas_register(), _rung_history())
    finally:
        PS.use_interpret(False)
    assert r["results"][0]["valid?"] is True
    assert r["results"][0]["backend"] == "device-batch"
    assert r["results"][1]["valid?"] is False
    collect = [s for s in trace.spans() if s.name == "batch.collect"]
    assert len(collect) == 1
    rungs = collect[0].args["rung_segments"]
    assert len(rungs) == PS.WIDE_RUNGS and rungs[1] >= 1, rungs
    assert collect[0].args["past_top"] == 0
