"""The benchmark's yardstick on the CPU: the traffic generator, the
plain reference, the exact statistics, and finding a cell's files by
name. None of it needs a chip."""

import json
import math
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def _bench():
    return harness.load_benchmark(ROOT)


# -- the generator --------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 3000000011])
def test_register_history_is_deterministic_per_seed(seed):
    a = gen.register_history(gen.rng_for(seed, 0), 10, 400, 5)
    b = gen.register_history(gen.rng_for(seed, 0), 10, 400, 5)
    c = gen.register_history(gen.rng_for(seed, 1), 10, 400, 5)
    assert a == b and a != c and len(a) == 400


@pytest.mark.parametrize("config", ["comdb2-register-50k"])
def test_history_has_the_config_op_mix_and_width(config):
    """The op mix and the processes are the source's own, with no cap
    on the calls in flight."""
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         config + ".json"))
    h = gen.register_history(gen.rng_for(5, 0), cfg["procs_per_key"],
                             40000, cfg["values"],
                             fs=tuple(cfg["op_mix"]))
    inv = [f for _, t, f, _ in h if t == "invoke"]
    for f in set(cfg["op_mix"]):
        want = cfg["op_mix"].count(f) / len(cfg["op_mix"])
        assert abs(inv.count(f) / len(inv) - want) < 0.02
    open_, worst = set(), 0
    for p, t, f, v in h:
        (open_.add if t == "invoke" else open_.discard)(p)
        worst = max(worst, len(open_))
    assert worst == cfg["procs_per_key"]


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("at", [0.0, 0.5, 0.99])
def test_generated_histories_are_valid_and_twins_invalid(seed, at):
    rng = gen.rng_for(seed, 3)
    h = gen.register_history(rng, 10, 600, 5, fs=("write", "cas", "cas",
                                                  "read"))
    assert reference.check(h) == (True, -1)
    twin, i = gen.corrupt(h, at)
    assert twin[i][3] == gen.NEVER_WRITTEN
    assert reference.check(twin) == (False, i)


def test_twin_sits_at_the_same_share_for_every_seed():
    """The corrupted read is the first ok read from the traffic's
    ``twin_at`` share on: every seed asks the same counterexample
    work, give or take the gap to the next read."""
    at = 0.5
    for seed in (1, 2**31 + 7, 3000000019):
        h = gen.register_history(gen.rng_for(seed, 0), 5, 2000, 5)
        _, i = gen.corrupt(h, at)
        assert 1000 <= i < 1040
        assert h[i][1:3] == ("ok", "read")
        assert not any(op[1:3] == ("ok", "read") for op in h[1000:i])


def test_edn_round_trips_through_the_program_parser():
    from comdb2_tpu.ops.history import parse_history

    h = gen.register_history(gen.rng_for(4, 0), 5, 50, 5)
    ops = parse_history(gen.to_edn(h))
    assert [(o.process, o.type, o.f, o.value) for o in ops] == h


# -- the reference ---------------------------------------------------------

W, C, R = "write", "cas", "read"


@pytest.mark.parametrize("history,want", [
    ([(0, "invoke", W, 1), (0, "ok", W, 1),
      (1, "invoke", R, None), (1, "ok", R, 1)], (True, -1)),
    # a stale read after a completed write
    ([(0, "invoke", W, 1), (0, "ok", W, 1),
      (0, "invoke", W, 2), (0, "ok", W, 2),
      (1, "invoke", R, None), (1, "ok", R, 1)], (False, 5)),
    # concurrent write lets the read see either value
    ([(0, "invoke", W, 1), (1, "invoke", R, None),
      (1, "ok", R, None), (0, "ok", W, 1)], (True, -1)),
    # cas from the wrong value succeeds: invalid
    ([(0, "invoke", W, 1), (0, "ok", W, 1),
      (1, "invoke", C, (2, 3)), (1, "ok", C, (2, 3))], (False, 3)),
    # a failed cas never happened
    ([(0, "invoke", W, 1), (0, "ok", W, 1),
      (1, "invoke", C, (2, 3)), (1, "fail", C, (2, 3)),
      (1, "invoke", R, None), (1, "ok", R, 1)], (True, -1)),
    # a crashed write may or may not have happened
    ([(0, "invoke", W, 4), (0, "info", W, 4),
      (1, "invoke", R, None), (1, "ok", R, 4)], (True, -1)),
])
def test_reference_on_hand_made_histories(history, want):
    assert reference.check(history) == want


def test_control_drops_the_read_guarantee_only():
    stale = [(0, "invoke", W, 1), (0, "ok", W, 1),
             (0, "invoke", W, 2), (0, "ok", W, 2),
             (1, "invoke", R, None), (1, "ok", R, 1)]
    assert reference.check(stale, weaken="reads") == (True, -1)
    bad_cas = [(0, "invoke", W, 1), (0, "ok", W, 1),
               (1, "invoke", C, (2, 3)), (1, "ok", C, (2, 3))]
    assert reference.check(bad_cas, weaken="reads") == (False, 3)


def test_reference_agrees_with_the_program_host_checker():
    from comdb2_tpu.checker.linear import analysis
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.op import Op

    rng = random.Random(99)
    for s in range(30):
        h = gen.register_history(gen.rng_for(s, 9), 5, 80, 5)
        oks = [i for i, o in enumerate(h) if o[1] == "ok"]
        i = rng.choice(oks)
        p, t, f, v = h[i]
        v = ((v[0] + 1) % 5, v[1]) if f == C else ((v or 0) + 1) % 5
        for hh in (h, h[:i] + [(p, t, f, v)] + h[i + 1:]):
            a = analysis(cas_register(), [Op(*o) for o in hh],
                         backend="host")
            got = (a.valid, a.op_index if a.valid is False else -1)
            assert got == reference.check(hh)


# -- statistics ------------------------------------------------------------

def test_nearest_rank_percentile_is_exact():
    v = list(range(1, 101))
    random.Random(0).shuffle(v)
    assert harness.percentile(v, 50) == 50
    assert harness.percentile(v, 95) == 95
    assert harness.percentile(v, 100) == 100
    assert harness.percentile([7.5], 95) == 7.5
    assert harness.percentile([], 95) is None
    assert harness.percentile([3, 1, 2], 50) == 2


def test_rate_and_span_share():
    assert harness.rate(100, 2.0, 4.0) == 50.0
    assert harness.rate(1, 3.0, 3.0) is None
    spans = harness.Spans()
    spans.groups["g"] = [(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]
    assert spans.share("g", 0.0, 4.0) == pytest.approx(75.0)
    assert spans.share("g", 1.5, 3.5) == pytest.approx(50.0)


def test_compare_counts_each_kind_of_wrong_answer():
    refs = {0: {0: (True, -1)}, 1: {0: (False, 7), 1: (True, -1)}}
    answers = [(0, {0: (True, -1)}),
               (1, {0: (False, 8), 1: (False, 3)}),
               (1, {0: ("unknown", -1), 1: (None, -1)}),
               (0, None)]
    c = harness.compare(answers, refs)
    assert c["verdict_mismatches"] == 1
    assert c["fail_index_mismatches"] == 1
    assert c["unknown_verdicts"] == 2
    assert c["unanswered"] == 1
    assert not harness.is_correct(c)
    assert harness.is_correct(harness.compare(answers[:1], refs))
    assert not harness.is_correct(harness.compare(answers[:1], refs, 1))
    assert not harness.is_correct(harness.compare(answers[2:3], refs))


# -- finding things by name -------------------------------------------------

def test_every_cell_finds_its_config_traffic_and_metrics():
    bench = _bench()
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert os.path.exists(os.path.join(
            BENCH, "drivers", cell.traffic["driver"] + ".py"))
        readers = cell.metric_readers()
        assert readers and all(callable(m.read) for m in readers.values())
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.per_layer:
            assert m["moves"] in names
            assert readers[m["name"]].LAYER == m["layer"]


def test_benchmark_json_keeps_to_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name_ok = lambda s: (len(s) <= 64 and s[0].isalnum() or s[0] == "_") \
        and all(c.isalnum() or c in "_.-" for c in s)
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and name_ok(c["name"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert c["file"].startswith("benchmark/")
    for w in bench["workloads"]:
        assert name_ok(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    assert not any(math.isnan(m.get("bound", 0)) for m in
                   bench["end_to_end"])
