"""Backend selection without hidden fallbacks, the compile cache's
placement, and one daemon per chip (ISSUE 21)."""

import os

import jax
import pytest

from comdb2_tpu.checker import pallas_seg as PS
from comdb2_tpu.checker import pallas_sort as PSORT
from comdb2_tpu.service import supervisor as SV
from comdb2_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- compile cache ------------------------------------------------------------

def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.compile_cache_dir() == str(tmp_path)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert platform.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = platform.compile_cache_dir()
    assert path == os.path.join(REPO, ".jax_cache")
    assert path == platform.DEFAULT_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


# --- backend selection --------------------------------------------------------

def test_select_backend_cpu_and_auto():
    assert platform.select_backend("cpu") == "cpu"
    assert platform.select_backend("auto") == "cpu"


def test_select_backend_tpu_refuses_cpu():
    with pytest.raises(SystemExit, match="tpu"):
        platform.select_backend("tpu")


# --- kernel probes: False off the TPU, raise on it ----------------------------

@pytest.fixture
def fake_tpu(monkeypatch):
    """The backend NAME says tpu; the kernels can't run (this is a CPU
    process), which is exactly a probe failure on a TPU."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    PS.available.cache_clear()
    PSORT.sort_pairs_available.cache_clear()
    yield
    PS.available.cache_clear()
    PSORT.sort_pairs_available.cache_clear()


def test_kernel_probes_answer_false_off_tpu():
    PS.available.cache_clear()
    PSORT.sort_pairs_available.cache_clear()
    assert PS.available() is False
    assert PSORT.sort_pairs_available() is False


def test_fused_kernel_probe_failure_raises_on_tpu(fake_tpu, monkeypatch):
    def broken(*a, **kw):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(PS, "check_device_pallas", broken)
    with pytest.raises(PS.KernelUnavailable, match="Mosaic refused"):
        PS.available()


def test_fused_kernel_probe_wrong_verdict_raises_on_tpu(fake_tpu,
                                                       monkeypatch):
    monkeypatch.setattr(PS, "check_device_pallas",
                        lambda *a, **kw: (PS.INVALID, 0, 0))
    with pytest.raises(PS.KernelUnavailable, match="expected VALID"):
        PS.available()


def test_sort_probe_failure_raises_on_tpu(fake_tpu):
    # a real Mosaic kernel on the CPU backend fails to lower: on a TPU
    # backend that must surface, not become a quiet lexsort
    with pytest.raises(RuntimeError, match="sort_pairs probe failed"):
        PSORT.sort_pairs_available()


def test_interpret_mode_refused_on_tpu(fake_tpu):
    with pytest.raises(RuntimeError, match="interpret"):
        PS.use_interpret(True)
    assert not PS.interpret_active()


# --- one daemon per chip ------------------------------------------------------

@pytest.fixture
def four_chips(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(platform, "host_tpu_chips", lambda: 4)


def test_supervisor_pins_each_daemon_to_its_own_chip(four_chips):
    sup = SV.Supervisor(min_daemons=1, max_daemons=4)
    assert sup.tpu_chips == 4
    for shard in range(4):
        chip = sup._free_chip()
        assert chip == shard
        sup.children[shard] = SV.Child(shard=shard, proc=None, port=0,
                                       service="x", t_spawn=0.0,
                                       chip=chip)
    with pytest.raises(OSError, match="one daemon per chip"):
        sup._free_chip()
    del sup.children[2]                 # a retired daemon frees its chip
    assert sup._free_chip() == 2
    env = SV.chip_env(2)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert len({SV.chip_env(c)["TPU_PROCESS_PORT"]
                for c in range(4)}) == 4


@pytest.mark.parametrize("kw", [
    {"max_daemons": 5},
    {"min_daemons": 5, "max_daemons": 5},
    {"max_daemons": 2, "daemon_args": ["--shards", "2"]},
], ids=["max", "min", "shards"])
def test_supervisor_refuses_more_daemons_than_chips(four_chips, kw):
    with pytest.raises(ValueError):
        SV.Supervisor(**kw)


def test_supervisor_cpu_daemons_are_not_pinned(four_chips):
    sup = SV.Supervisor(max_daemons=8,
                        daemon_args=["--backend", "cpu"])
    assert sup.tpu_chips == 0 and sup._free_chip() is None

