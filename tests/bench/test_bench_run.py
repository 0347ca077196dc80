"""The whole of a run, on the CPU at a size a test can hold: the look
for a chip is skipped, and everything after it runs as on the chip.
The control and each fault the timed path can have must come out not
correct; the unbroken program must come out correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import run  # noqa: E402

#: per config: the keys cut so that a CPU run takes a second
TINY = {"comdb2-register-50k": {"ops_per_key": 80}}
POSTHOC = ["register-50k.posthoc"]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout of the benchmark whose configurations are cut to
    test size."""
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(BENCH, "traffic"),
                    root / "benchmark" / "traffic")
    (root / "benchmark" / "configs").mkdir()
    bench = harness.load_benchmark(ROOT)
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        cfg.update(TINY.get(c["name"], {}))
        (root / c["file"]).write_text(json.dumps(cfg))
    return str(root)


def _run(root, capsys, workload, seed, *extra, trace=0):
    args = run.parser().parse_args(
        ["--workload", workload, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace), *extra])
    capsys.readouterr()
    rc = run.run(args, require_chip=False, root=root)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(x) for x in out]


@pytest.mark.parametrize("workload", POSTHOC)
def test_sound_program_is_correct(tiny_root, capsys, workload):
    rc, lines = _run(tiny_root, capsys, workload, 2**31 + 17)
    res = lines[-1]
    assert rc == 0 and res["correct"] is True, res
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"check_ops_per_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] > 0
    kinds = [x.get("line") for x in lines[:-1]]
    assert kinds[:2] == ["setup", "compiles_in_window"]
    assert lines[1]["xla_lowerings"] == 0


@pytest.mark.parametrize("workload", POSTHOC)
def test_control_is_not_correct(tiny_root, capsys, workload):
    rc, lines = _run(tiny_root, capsys, workload, 2**31 + 18, "--control")
    res = lines[-1]
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["verdict_mismatches"]["value"] > 0


def _flip_verdicts(monkeypatch):
    """A wrong answer made where it is produced."""
    from comdb2_tpu.checker import linear

    orig = linear.analysis

    def flipped(*a, **kw):
        r = orig(*a, **kw)
        r.valid = not r.valid if isinstance(r.valid, bool) else r.valid
        if r.valid is False and r.op_index is None:
            r.op_index = 0
        return r
    monkeypatch.setattr(linear, "analysis", flipped)


def _state_unchanged(monkeypatch):
    """A search step that hands its frontier back unchanged: nothing
    is ever ruled out, every history reads valid."""
    from comdb2_tpu.checker import linear, linear_host, linear_jax

    monkeypatch.setattr(
        linear_host, "check",
        lambda mm, packed, **kw: linear_host.HostResult(
            valid=True, op_index=None, configs=[], final_count=1,
            max_frontier=1, pre_configs=[]))
    orig = linear._device_verdict

    def keep_state(mm, packed, segs, status, fail_seg, n_final, info):
        return orig(mm, packed, segs, linear_jax.VALID, -1, n_final, info)
    monkeypatch.setattr(linear, "_device_verdict", keep_state)


def _give_up(monkeypatch):
    """The checker gives up early: every analysis answers unknown. The
    reference answers exactly, so this is a wrong answer, and it is no
    work done: it counts in ``failed`` and not in the rate."""
    from comdb2_tpu.checker import linear

    orig = linear.analysis

    def unknown(*a, **kw):
        r = orig(*a, **kw)
        r.valid = linear.UNKNOWN
        return r
    monkeypatch.setattr(linear, "analysis", unknown)


def _compile_in_window(monkeypatch):
    """Each check lowers a program of a shape never seen before: the
    window compiles, and its numbers do not measure the cell."""
    import jax
    import jax.numpy as jnp

    from comdb2_tpu.checker import linear

    orig = linear.analysis
    n = [0]

    def compiling(*a, **kw):
        n[0] += 1
        jax.jit(lambda x: x * 3 + 1)(jnp.zeros(1000 + n[0])) \
            .block_until_ready()
        return orig(*a, **kw)
    monkeypatch.setattr(linear, "analysis", compiling)


FAULTS = [("register-50k.posthoc", _flip_verdicts),
          ("register-50k.posthoc", _state_unchanged),
          ("register-50k.posthoc", _give_up)]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_each_fault_is_not_correct(tiny_root, capsys, monkeypatch,
                                   workload, fault):
    fault(monkeypatch)
    rc, lines = _run(tiny_root, capsys, workload, 2**31 + 19)
    assert rc == 0 and lines[-1]["correct"] is False, lines[-1]
    if fault is _give_up:
        res = lines[-1]
        assert res["checks"]["unknown_verdicts"]["value"] > 0
        assert res["failed"] == res["attempted"] > 0
        # no exact verdict came back: no rate to report
        assert "check_ops_per_s" not in res["metrics"]


@pytest.mark.parametrize("workload", POSTHOC)
def test_compile_in_window_is_not_correct(tiny_root, capsys, monkeypatch,
                                          workload):
    rc, lines = _run(tiny_root, capsys, workload, 2**31 + 21)
    assert lines[-1]["correct"] is True
    _compile_in_window(monkeypatch)
    rc, lines = _run(tiny_root, capsys, workload, 2**31 + 21)
    res = lines[-1]
    assert rc == 0 and res["correct"] is False, res
    assert res["checks"]["window_compiles"]["value"] > 0
    assert res["checks"]["verdict_mismatches"]["value"] == 0


def test_traced_run_reports_per_layer_metrics(tiny_root, capsys):
    rc, lines = _run(tiny_root, capsys, "register-50k.posthoc",
                     2**31 + 20, trace=1)
    res = lines[-1]
    assert rc == 0 and res["correct"] is True
    # on the CPU there is no device plane: the trace metrics read
    # nothing and are left out; the host spans still read
    assert set(res["metrics"]) == {"ingest_share.posthoc",
                                   "segment_share.posthoc"}
    assert all(0 < m["value"] < 100 for m in res["metrics"].values())


def test_missing_wrapped_function_reads_nothing(tiny_root, capsys,
                                                monkeypatch):
    from comdb2_tpu.checker import linear

    monkeypatch.delattr(linear, "make_memo")
    spans = harness.Spans()
    spans.install("ingest", ["comdb2_tpu.checker.linear.make_memo",
                             "comdb2_tpu.ops.packed.pack_history"])
    try:
        assert spans.missing == {
            "ingest": ["comdb2_tpu.checker.linear.make_memo"]}
        assert spans.share("ingest", 0.0, 1.0) is None
    finally:
        spans.uninstall()


def _cli(cwd, *argv):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *argv], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _cli(ROOT, "--workload", "register-50k.posthoc", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's own
    files has no program to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path), "--workload", "register-50k.posthoc",
             "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
