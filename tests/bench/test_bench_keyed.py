"""The keyed cell (``register-keyed.posthoc``) on the CPU: its
generator, its plain reference and its driver, at a size a test can
hold. The sound program comes out correct, the control does not; the
twin sits on the same key at the same share for every seed; and a
warm-up that outlasts its budget raises."""

import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import gen_keyed  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import reference_keyed  # noqa: E402
import run  # noqa: E402

CELL = "register-keyed.posthoc"
CFG = harness.load_json(os.path.join(
    BENCH, "configs", "comdb2-register-keyed.json"))
TRAFFIC = harness.load_json(os.path.join(BENCH, "traffic", "keyed.json"))
#: the keyed configuration cut so that a CPU run takes seconds; the
#: twin moves to a key that exists at that size
TINY_CFG = {"keys": 3, "ops_per_key": 40}
TINY_TRAFFIC = {"twin_key": 1, "pool": 2}
SMALL = dict(CFG, keys=4, ops_per_key=150)


def _twin():
    return (int(TRAFFIC["twin_key"]), float(TRAFFIC["twin_at"]))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_keyed_root")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(BENCH, "traffic"),
                    root / "benchmark" / "traffic")
    tr = dict(TRAFFIC, **TINY_TRAFFIC)
    (root / "benchmark" / "traffic" / "keyed.json").write_text(
        json.dumps(tr))
    (root / "benchmark" / "configs").mkdir()
    for c in harness.load_benchmark(ROOT)["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        if c["name"] == CFG["name"]:
            cfg.update(TINY_CFG)
        (root / c["file"]).write_text(json.dumps(cfg))
    return str(root)


def _run(root, capsys, seed, *extra):
    args = run.parser().parse_args(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", "0", *extra])
    capsys.readouterr()
    rc = run.run(args, require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(x) for x in out]


def test_sound_program_is_correct(tiny_root, capsys):
    rc, lines = _run(tiny_root, capsys, 2**31 + 26)
    res = lines[-1]
    assert rc == 0 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["window_compiles"]["value"] == 0
    ref = next(x for x in lines if x.get("line") == "reference")
    assert ref["compared"] == TINY_CFG["keys"] * ref["pool_entries_checked"]


def test_control_is_not_correct(tiny_root, capsys):
    rc, lines = _run(tiny_root, capsys, 2**31 + 27, "--control")
    res = lines[-1]
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["verdict_mismatches"]["value"] > 0


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3, 3300000001,
                                  2**33 + 17])
def test_keyed_generator_is_deterministic_per_seed(seed):
    a, ca = gen_keyed.keyed_history(seed, 0, SMALL, _twin()[:1] + (0.5,))
    b, cb = gen_keyed.keyed_history(seed, 0, SMALL, _twin()[:1] + (0.5,))
    assert a == b and ca == cb
    other, _ = gen_keyed.keyed_history(seed + 1, 0, SMALL)
    assert other != a
    # keys follow one another, each with its own ten processes
    keys = [v[0] for _, _, _, v in a]
    assert keys == sorted(keys) and set(keys) == set(range(4))
    for k in range(4):
        procs = {p for p, _, _, v in a if v[0] == k}
        assert procs <= set(range(10 * k, 10 * k + 10))
        n_events = sum(1 for _, _, _, v in a if v[0] == k)
        assert n_events == 2 * SMALL["ops_per_key"]


@pytest.mark.parametrize("seed", [1, 2**31 + 9, 2**36 + 1, 3300000011,
                                  2**41 + 7])
def test_twin_is_the_same_key_at_the_same_share_for_every_seed(seed):
    cfg = dict(CFG, keys=20, ops_per_key=100)
    twin_key, at = _twin()
    h, changed = gen_keyed.keyed_history(seed, 0, cfg, (twin_key, at))
    refs = reference_keyed.check(h)
    bad = [k for k, (v, _) in refs.items() if not v]
    assert bad == [twin_key]
    n = 2 * cfg["ops_per_key"]
    assert at * n <= changed < at * n + 40
    # the reference fails exactly at the corrupted read
    assert refs[twin_key][1] == changed
    plain, _ = gen_keyed.keyed_history(seed, 0, cfg)
    assert all(v for v, _ in reference_keyed.check(plain).values())


def test_reference_split_is_the_per_key_walk():
    h, _ = gen_keyed.keyed_history(11, 0, SMALL, (2, 0.5))
    h = h[:50] + [(99, "info", "start", None)] + h[50:]
    subs = reference_keyed.split(h)
    assert list(subs) == [0, 1, 2, 3]
    for k, sub in subs.items():
        want = [(p, t, f, v[1]) if isinstance(v, tuple) else (p, t, f, v)
                for p, t, f, v in h
                if not isinstance(v, tuple) or v[0] == k]
        assert sub == want


def test_reference_fail_index_is_the_program_op_index():
    """The convention both hold to: a key's fail index is the position
    of the failing ok inside that key's own subhistory — the program's
    per-key ``op-index``."""
    from comdb2_tpu.checker import independent
    from comdb2_tpu.checker.checkers import Linearizable
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.kv import tuple_
    from comdb2_tpu.ops.op import Op

    cfg = dict(CFG, keys=3, ops_per_key=30)
    h, changed = gen_keyed.keyed_history(5, 0, cfg, (1, 0.5))
    want = reference_keyed.check(h)
    ops = [Op(p, t, f, tuple_(*v)) for p, t, f, v in h]
    got = independent.checker(Linearizable(backend="host")).check(
        {}, cas_register(), ops)["results"]
    assert want[1] == (False, changed)
    for k, (valid, at) in want.items():
        assert got[k]["valid?"] is valid
        if not valid:
            assert got[k]["op-index"] == at
            assert reference.check(reference_keyed.split(h)[k])[1] == at


def _driver(budget, passes=2, pool=2):
    mod = harness.load_driver("keyed")

    class Cell:
        config = dict(CFG, keys=2, ops_per_key=40)
        traffic = dict(TRAFFIC, pool=pool, twin_key=1,
                       warmup_budget_s=budget, warmup_passes=passes)

    d = mod.Driver(Cell, 3, None)
    d.generate()
    return mod, d


@pytest.mark.parametrize("how", ["between-checks", "inside-a-check"])
def test_warmup_budget_raises_on_a_slow_entry(how):
    mod, d = _driver(budget=0.3)
    if how == "between-checks":
        d.make_entry = lambda control=False: (lambda j: time.sleep(0.2))
    else:
        d.make_entry = lambda control=False: (lambda j: time.sleep(5))
    t0 = time.perf_counter()
    with pytest.raises(mod.WarmupBudgetExceeded):
        d.warm_up()
    assert time.perf_counter() - t0 < 2.0


def test_warmup_within_budget_runs_every_pass():
    mod, d = _driver(budget=30, passes=2, pool=3)
    seen = []
    d.make_entry = lambda control=False: seen.append
    d.warm_up()
    assert seen == [0, 1, 2, 0, 1, 2]
