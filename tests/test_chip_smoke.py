"""chip_smoke.py: no result without a TPU, and its phases' own checks
hold on the CPU mesh at small sizes with the kernel in interpret mode
(the chip run is the real one; this keeps the script's logic green at
tier 1)."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke as CS
from comdb2_tpu.checker import pallas_seg as PS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exits_nonzero_without_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_exits_nonzero_outside_a_checkout(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        open(os.path.join(ROOT, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and r.stdout == ""


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(CS, "B_EVENTS", 100)
    monkeypatch.setattr(CS, "MULTI_HISTS", 64)
    PS.use_interpret(True)
    yield
    PS.use_interpret(False)


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def test_phases_hold_on_cpu_mesh(interpret, capsys):
    devs = jax.devices()
    CS.phase_batch(7, 96, devs[:1])
    CS.phase_served(7, devs[:1])
    CS.phase_multichip(7, devs[:CS.MULTI_DEVICES])
    batch, served, multi = _lines(capsys)
    assert batch["engine"] == "stream"
    assert batch["oracle_agree"] == batch["oracle_sample"] == 20
    assert served["replies"]["txn-g2"]["anomaly_class"] == "G2-item"
    assert multi["bit_identical"] is True
    assert sorted(multi["histories_per_device"].values()) == [16] * 4
    for line in (batch, served, multi):
        assert line["compile_offenders"] == [], line["phase"]


def test_failed_check_raises_and_is_reported(interpret, capsys,
                                             monkeypatch):
    from comdb2_tpu.checker import linear_host

    class Wrong:
        valid = False

    # an oracle that disagrees must fail the phase, with its line
    monkeypatch.setattr(linear_host, "check", lambda *a, **k: Wrong())
    with pytest.raises(CS.SmokeFailure, match="oracle"):
        CS.phase_batch(7, 96, jax.devices()[:1])
    (line,) = _lines(capsys)
    assert "oracle" in line["error"]
