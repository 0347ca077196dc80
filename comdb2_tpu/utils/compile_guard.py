"""Runtime compile-surface guard.

The static inventory (:mod:`comdb2_tpu.analysis.compile_surface`)
declares the CLOSED program set every serving surface may compile;
this module observes what actually compiles so a recompile storm is a
red test (or a failed bench run), not a 38-minute mystery:

- :class:`CompileGuard` captures one :class:`CompileRecord` per XLA
  LOWERING — jax logs ``Compiling jit(<name>) with global shapes
  ...`` per
  distinct (function, shape signature) when ``jax_log_compiles`` is
  on; lowerings are the right unit because a shape-churned workload
  re-lowers even when the persistent program cache absorbs the
  backend compile.
- Module counters mirror the ``DISPATCHES``-style dispatch counters:
  ``XLA_COMPILES`` here, ``pallas_seg.MOSAIC_BUILDS`` (one per fused-
  kernel program built — a Mosaic compile per distinct
  ``(SegKernelSpec, b_pad, stream)``), ``closure_jax.COMPILES`` (one
  per txn closure N-bucket program).
- :func:`CompileGuard.offenders` / :func:`assert_closed` check the
  observed set against the static inventory — tier-1 runs a
  mixed-shape workload under the guard and asserts observed ⊆
  declared; the bench scripts do the same on real runs (env
  ``COMDB2_TPU_COMPILE_GUARD=0`` disables the bench assertion, never
  the capture).

Usage::

    from comdb2_tpu.utils import compile_guard
    with compile_guard.guard() as g:
        ...                       # any checker/service/shrink work
    g.assert_closed()             # raises CompileSurfaceError

Single-threaded by design (this container exposes ONE CPU and the
service core is single-threaded); nested guards each see their own
window of records.
"""

from __future__ import annotations

import logging
import os
import re
from contextlib import contextmanager
from dataclasses import dataclass
from typing import List, Optional, Tuple

#: process-global lowering counter (mirrors txn.closure_jax.DISPATCHES)
XLA_COMPILES = 0

#: active guards, outermost first — only the outermost increments the
#: global counter (with nested guards every attached handler sees
#: every log record; per-guard records stay per-window)
_ACTIVE: list = []

#: jax logger that emits the per-lowering line
_COMPILE_LOGGER = "jax._src.interpreters.pxla"
#: with jax_log_compiles on, this logger also chats per trace at
#: WARNING; attach the (non-parsing) handler there too so logging's
#: last-resort stderr handler stays quiet during the guard window
_NOISY_LOGGERS = ("jax._src.dispatch",)

#: jax 0.9: ``Compiling jit(f) with global shapes and types
#: (ShapedArray(float32[24,24]),). Argument mapping: ...``
_COMPILE_RE = re.compile(
    r"Compiling jit\((.+?)\) with global shapes and types \((.*?)\)"
    r"\. Argument mapping", re.DOTALL)
_SHAPED_RE = re.compile(r"ShapedArray\((\w+)\[([0-9,\s]*)\]")


@dataclass(frozen=True)
class CompileRecord:
    """One observed XLA lowering: jit name + traced arg shapes."""

    name: str
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]

    def format(self) -> str:
        args = ", ".join(
            f"{dt}[{','.join(map(str, sh))}]"
            for dt, sh in zip(self.dtypes, self.shapes))
        return f"{self.name}({args})"


def parse_compile_log(message: str) -> Optional[CompileRecord]:
    """Parse one ``Compiling jit(<name>) with global shapes and types
    (...)`` log message (None for other messages)."""
    m = _COMPILE_RE.search(message)
    if not m:
        return None
    name = m.group(1)
    shapes: List[Tuple[int, ...]] = []
    dtypes: List[str] = []
    for dm in _SHAPED_RE.finditer(m.group(2)):
        dtypes.append(dm.group(1))
        dims = dm.group(2).strip()
        shapes.append(tuple(int(d) for d in dims.split(","))
                      if dims else ())
    return CompileRecord(name=name, shapes=tuple(shapes),
                         dtypes=tuple(dtypes))


class CompileSurfaceError(AssertionError):
    """Observed compiles escaped the declared static inventory."""


class CompileGuard(logging.Handler):
    """Captures every XLA lowering in its window as a
    :class:`CompileRecord`. A ``logging.Handler`` attached to jax's
    lowering logger — attaching a handler also keeps the records off
    stderr (logging's last-resort handler only fires when NO handler
    is attached)."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.records: List[CompileRecord] = []
        self._counters0: dict = {}

    # -- logging.Handler ------------------------------------------------

    def emit(self, record: logging.LogRecord) -> None:
        global XLA_COMPILES
        try:
            rec = parse_compile_log(record.getMessage())
        except Exception:               # noqa: BLE001 — never raise
            return                      # from a logging handler
        if rec is not None:
            if _ACTIVE and _ACTIVE[0] is self:
                XLA_COMPILES += 1
            self.records.append(rec)

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "CompileGuard":
        import jax

        from ..checker import pallas_seg as PS
        from ..txn import closure_jax as CJ

        self._counters0 = {"mosaic": PS.MOSAIC_BUILDS,
                           "closure": CJ.COMPILES}
        self._prev_flag = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._prev_propagate = {}
        for name in (_COMPILE_LOGGER,) + _NOISY_LOGGERS:
            lg = logging.getLogger(name)
            lg.addHandler(self)
            # stop propagation to root/absl handlers for the window:
            # attaching a handler only silences logging's last-resort
            # handler, not an installed root handler — without this,
            # every lowering sprays WARNING lines into bench stderr
            self._prev_propagate[name] = lg.propagate
            lg.propagate = False
        _ACTIVE.append(self)
        return self

    def stop(self) -> None:
        import jax

        if self in _ACTIVE:
            _ACTIVE.remove(self)
        for name in (_COMPILE_LOGGER,) + _NOISY_LOGGERS:
            lg = logging.getLogger(name)
            lg.removeHandler(self)
            # nested guards: the logger stays non-propagating until
            # the LAST guard touching it detaches
            if not any(isinstance(h, CompileGuard) for h in
                       lg.handlers):
                lg.propagate = self._prev_propagate.get(name, True)
        jax.config.update("jax_log_compiles", self._prev_flag)

    # -- reporting ------------------------------------------------------

    def counters(self) -> dict:
        """Lowering/build counts inside this guard's window."""
        from ..checker import pallas_seg as PS
        from ..txn import closure_jax as CJ

        return {
            "xla_lowerings": len(self.records),
            "mosaic_builds": PS.MOSAIC_BUILDS
            - self._counters0.get("mosaic", 0),
            "closure_programs": CJ.COMPILES
            - self._counters0.get("closure", 0),
        }

    def offenders(self, inventory=None) -> List[CompileRecord]:
        """Observed records OUTSIDE the declared compile surface."""
        if inventory is None:
            from ..analysis.compile_surface import static_inventory

            inventory = static_inventory()
        return inventory.offenders(self.records)

    def assert_closed(self, inventory=None) -> None:
        off = self.offenders(inventory)
        if off:
            raise CompileSurfaceError(
                "observed compiles escaped the static inventory "
                "(unbucketed shapes reached a jit boundary):\n  "
                + "\n  ".join(r.format() for r in off))

    def summary(self, inventory=None) -> dict:
        """JSON-able guard report (bench artifacts embed this)."""
        off = self.offenders(inventory)
        return {
            **self.counters(),
            "compile_surface_ok": not off,
            "offenders": [r.format() for r in off],
        }


@contextmanager
def guard():
    """``with compile_guard.guard() as g: ...`` — capture every XLA
    lowering in the block."""
    g = CompileGuard().start()
    try:
        yield g
    finally:
        g.stop()


def enabled() -> bool:
    """Whether bench runs should ASSERT surface closure (capture is
    always on there; ``COMDB2_TPU_COMPILE_GUARD=0`` turns the hard
    assert into report-only)."""
    return os.environ.get("COMDB2_TPU_COMPILE_GUARD", "1") != "0"


__all__ = ["CompileGuard", "CompileRecord", "CompileSurfaceError",
           "XLA_COMPILES", "enabled", "guard", "parse_compile_log"]
