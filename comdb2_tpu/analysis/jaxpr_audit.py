"""Jaxpr recompile-hazard analysis.

XLA compiles one program per distinct input shape, and compile time
scales with BOTH scan length and frontier width — so production code
buckets every shape that reaches a jit boundary (pow2 pads, the fixed
fuzz bucket ladder). This pass enforces that discipline statically:

- :func:`scan_files` — AST scan of the fuzz script and the driver:
  ``bucket = (a, b)`` literals must come from the declared ladder;
  literal ``s_pad``/``k_pad`` values at ``make_segments`` call sites
  must be powers of two (non-literal pads must route through
  ``next_pow2``); literal ``n_states``/``n_transitions`` at engine
  entry calls must be bucketed. An unbucketed shape means one
  compiled program PER SEED — fuzz runs recompile per seed and can
  OOM LLVM.
- :func:`check_bucket_closure` — the declared ladder must be closed
  under the kernel gate: every bucket must fit ``spec_for`` (else the
  fuzz silently skips whole families) and the table budget.
- :func:`trace_entry_points` — abstractly traces the engine entry
  points (``checker/linear_jax.py`` seg engines, ``checker/batch.py``)
  across the declared buckets on the CPU backend (tracing only — no
  compile, no chip) and flags duplicated sub-jaxprs under
  ``cond`` branches: the same closure body inlined under two branches
  of nested ``lax.cond`` explodes CPU compile time (CLAUDE.md; the
  two-tier engine runs the small tier unconditionally for exactly
  this reason).
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Tuple

from . import Finding, suppressed
from .pallas_budget import PRODUCTION_BUCKETS, _fold, _fold_tuple, \
    _module_consts

#: pads the fuzz script may use literally (everything else must route
#: through next_pow2)
DECLARED_PADS = {"s_pad": {64}, "k_pad": {8}}

#: engine entry points traced per bucket: (module, attr, P)
TRACE_ENTRY_POINTS = (
    ("comdb2_tpu.checker.linear_jax", "check_device_seg", 4),
    ("comdb2_tpu.checker.linear_jax", "check_device_seg2", 4),
)

#: a cond branch with at least this many equations is "non-trivial" —
#: pass-through branches (lambda _: carry) legitimately repeat
MIN_BRANCH_EQNS = 3

S_PAD, K_PAD = 64, 8


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


# --- AST scan ---------------------------------------------------------------

ENTRY_CALL_NAMES = {"check_device_seg", "check_device_seg2",
                    "check_device_pallas", "check_device_seg_batch",
                    "check_device_pallas_stream", "pad_succ"}


def scan_file(path: str, source: Optional[str] = None, *,
              apply_suppressions: bool = True) -> List[Finding]:
    if source is None:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return []
    lines = source.splitlines()
    env = _module_consts(tree)
    raw: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "bucket":
            dims = _fold_tuple(node.value, env)
            if dims is not None and len(dims) == 2 \
                    and tuple(dims) not in PRODUCTION_BUCKETS:
                raw.append(Finding(
                    "jaxpr-unbucketed-shape", path, node.lineno,
                    f"bucket {dims} is not in the declared ladder "
                    f"{list(PRODUCTION_BUCKETS)} — an unbucketed "
                    "shape compiles one program per seed (recompiles "
                    "can OOM LLVM)"))
            continue
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else \
            (fn.id if isinstance(fn, ast.Name) else "")
        if name == "make_segments":
            for kw in node.keywords:
                if kw.arg in DECLARED_PADS:
                    v = _fold(kw.value, env)
                    if v is not None and not _is_pow2(v):
                        raw.append(Finding(
                            "jaxpr-unbucketed-shape", path,
                            node.lineno,
                            f"{kw.arg}={v} is not a power of two — "
                            "pads must be bucketed (next_pow2) so "
                            "histories share compiled programs"))
        elif name in ENTRY_CALL_NAMES:
            for kw in node.keywords:
                if kw.arg in ("n_states", "n_transitions"):
                    v = _fold(kw.value, env)
                    if v is not None and not _is_pow2(v):
                        raw.append(Finding(
                            "jaxpr-unbucketed-shape", path,
                            node.lineno,
                            f"{kw.arg}={v} at a jit boundary is not "
                            "a pow2 bucket — shape buckets must be "
                            "closed"))
    if not apply_suppressions:
        return raw
    return [f for f in raw if not suppressed(lines, f.line, f.rule)]


def scan_files(paths: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        if os.path.exists(p):
            out += scan_file(p)
    return out


# --- bucket closure ---------------------------------------------------------

def check_bucket_closure() -> List[Finding]:
    """The declared ladder must be kernel-eligible end to end: every
    bucket fits the fused kernel's table budget and ``spec_for``
    accepts it at the (8,128)-tier slot counts, so no family silently
    falls off the device path (round-2 Weak #1 was exactly that:
    10/120 queue seeds device-checked)."""
    from ..checker import pallas_seg as PS

    path = PS.__file__
    out: List[Finding] = []
    for ns, nt in PRODUCTION_BUCKETS:
        if ns * nt > PS.MAX_TABLE:
            out.append(Finding(
                "jaxpr-bucket-closure", path, 0,
                f"bucket ({ns},{nt}) exceeds the kernel table budget "
                f"MAX_TABLE={PS.MAX_TABLE}"))
            continue
        if PS.spec_for(ns, nt, 4, K_PAD) is None:
            out.append(Finding(
                "jaxpr-bucket-closure", path, 0,
                f"bucket ({ns},{nt}) is rejected by spec_for at "
                f"P=4/K={K_PAD} — the fuzz ladder and the kernel "
                "gate have drifted apart"))
    return out


# --- abstract tracing -------------------------------------------------------

def _force_cpu() -> bool:
    """Pin jax to the CPU backend (tracing must never take the chip).
    Returns False when a non-CPU backend was already initialized —
    callers then skip tracing."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass
    return jax.default_backend() == "cpu"


def _walk_jaxprs(jaxpr):
    """Yield every (sub-)jaxpr reachable through eqn params."""
    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                for cand in (v if isinstance(v, (tuple, list))
                             else (v,)):
                    inner = getattr(cand, "jaxpr", cand)
                    if hasattr(inner, "eqns"):
                        stack.append(inner)


def duplicated_cond_branches(closed_jaxpr) -> List[str]:
    """Descriptions of cond equations whose non-trivial branches are
    structurally identical (each compiles separately: the nested-cond
    compile explosion)."""
    out: List[str] = []
    for j in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name != "cond":
                continue
            branches = eqn.params.get("branches", ())
            seen: Dict[str, int] = {}
            for bi, br in enumerate(branches):
                inner = getattr(br, "jaxpr", br)
                if len(inner.eqns) < MIN_BRANCH_EQNS:
                    continue
                key = str(inner)
                if key in seen:
                    out.append(
                        f"cond branches {seen[key]} and {bi} are "
                        f"structurally identical "
                        f"({len(inner.eqns)} eqns)")
                else:
                    seen[key] = bi
    return out


def trace_entry_points(
        buckets: Sequence[Tuple[int, int]] = PRODUCTION_BUCKETS
) -> List[Finding]:
    """Abstractly trace the engine entry points for every declared
    bucket; flag trace failures and duplicated cond sub-jaxprs.
    Tracing builds the jaxpr only — no XLA compile, no device."""
    import importlib

    if not _force_cpu():
        return [Finding(
            "jaxpr-trace-failure", __file__, 0,
            "a non-CPU jax backend was initialized before the audit "
            "could pin the platform — run with JAX_PLATFORMS=cpu")]
    import jax
    import numpy as np

    from ..checker import pallas_seg as PS

    out: List[Finding] = []
    for mod_name, attr, P in TRACE_ENTRY_POINTS:
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)
        path = mod.__file__
        for ns, nt in buckets:
            args = (np.zeros((ns, nt), np.int32),          # succ
                    np.zeros((S_PAD, K_PAD), np.int32),    # inv_proc
                    np.zeros((S_PAD, K_PAD), np.int32),    # inv_tr
                    np.zeros(S_PAD, np.int32),             # ok_proc
                    np.zeros(S_PAD, np.int32))             # depth
            # the kernel's rung-0 frontier: the capacity at which the
            # seg engines are compared with the fused kernel segment
            # by segment (its wide rungs run past it inside the kernel)
            kw = dict(F=PS.F, P=P, n_states=ns, n_transitions=nt)
            try:
                jaxpr = jax.make_jaxpr(
                    lambda *a: fn(*a, **kw))(*args)
            except Exception as e:            # trace failure IS a finding
                out.append(Finding(
                    "jaxpr-trace-failure", path, 0,
                    f"{attr} failed to trace at bucket ({ns},{nt}): "
                    f"{type(e).__name__}: {e}"))
                continue
            for desc in duplicated_cond_branches(jaxpr):
                out.append(Finding(
                    "jaxpr-dup-cond", path, 0,
                    f"{attr} at bucket ({ns},{nt}): {desc} — run the "
                    "shared tier unconditionally and select with ONE "
                    "cond"))
    return out


from . import Pass, register_pass


def _repo_stage(ctx):
    # bucket-closure scan of the fuzz script and the chunked driver,
    # plus (with trace) abstract traces of the engine entry points
    out = scan_files(
        [os.path.join(ctx["root"], "scripts", "fuzz_pallas_seg.py"),
         os.path.join(ctx["root"], "comdb2_tpu", "checker",
                      "linear.py")])
    out += check_bucket_closure()
    if ctx["trace"]:
        out += trace_entry_points()
    return out


register_pass(Pass(
    name="jaxpr-audit",
    scan_paths=scan_files,
    raw_file=lambda path, source: scan_file(
        path, source, apply_suppressions=False),
    repo_stage=_repo_stage,
))
