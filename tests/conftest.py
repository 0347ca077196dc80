"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware; the chip itself is reached only through
``python chip_smoke.py`` on a machine that has one.

``JAX_PLATFORMS=cpu`` is set before jax is imported and confirmed
through the config API, and the persistent compile cache goes where
``utils.platform.enable_compile_cache`` puts it
(``JAX_COMPILATION_CACHE_DIR`` or the checkout's ``.jax_cache``).
"""

import os
import sys

# env vars still matter for subprocesses and not-yet-imported jax
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from comdb2_tpu.utils.platform import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu", (
    f"tests must run on the CPU mesh, got {jax.default_backend()!r} — "
    "a backend was initialized before conftest could force the platform")
assert len(jax.devices()) == 8, jax.devices()


# --- native components -------------------------------------------------------
#
# The cluster, pmux and loader tests skip when ``native/build`` lacks
# their binaries, and that directory is not tracked: a clean checkout
# would silently lose them. Build once per session, before any test
# module is collected, in the xdist controller only (workers inherit
# the finished tree), under a file lock so concurrent sessions never
# race on one build directory. A missing toolchain or a failed build
# leaves the skips in place and says why in one line.

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
NATIVE_BUILD = os.path.join(NATIVE, "build")
NATIVE_TARGETS = ("sut_node", "sut_server", "ct_pmux", "libct_sut.so")
NATIVE_BUILD_TIMEOUT_S = 300


def _native_missing():
    return [t for t in NATIVE_TARGETS
            if not os.path.exists(os.path.join(NATIVE_BUILD, t))]


def build_native(timeout_s=NATIVE_BUILD_TIMEOUT_S):
    """Configure and build ``native/`` when a binary the tests need is
    missing. Returns None on success, else one line saying why not."""
    import fcntl
    import shutil
    import subprocess
    import time

    if not _native_missing():
        return None
    if shutil.which("cmake") is None:
        return "cmake not found"
    os.makedirs(NATIVE_BUILD, exist_ok=True)
    deadline = time.monotonic() + timeout_s
    with open(os.path.join(NATIVE_BUILD, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not _native_missing():       # another session built it
            return None
        jobs = str(min(os.cpu_count() or 1, 8))
        for cmd in (["cmake", "-S", NATIVE, "-B", NATIVE_BUILD],
                    ["cmake", "--build", NATIVE_BUILD, "--parallel",
                     jobs]):
            left = deadline - time.monotonic()
            try:
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=max(left, 1))
            except subprocess.TimeoutExpired:
                return f"`{' '.join(cmd[:2])}` outlasted {timeout_s} s"
            if r.returncode != 0:
                tail = (r.stderr or r.stdout).strip().splitlines()
                return (f"`{' '.join(cmd[:2])}` exited {r.returncode}: "
                        f"{tail[-1] if tail else 'no output'}")
    missing = _native_missing()
    return f"built, but still missing {missing}" if missing else None


def pytest_configure(config):
    if hasattr(config, "workerinput"):
        return                          # the controller builds
    why = build_native()
    if why is not None:
        sys.stderr.write(f"native build skipped ({why}); the native "
                         "tests stay skipped\n")
