"""Static Pallas/Mosaic resource budgeting.

Checks kernel configurations against the measured v5e limits BEFORE a
40 s Mosaic compile fails (or worse, a 2048-step grid "Exceeded smem
capacity" lands in a fuzz loop):

- scalar-prefetch SMEM holds ~14336 int32 (~56 KB; 2048x10 fails);
- SMEM is ALSO bounded per grid step (~500 B/step toward the 1 MB
  space): a 2048-step grid fails compile while 1408 steps pass — the
  production CHUNK stays at 1024;
- grid-step blocks need (sublane, lane) dims that divide or are
  multiples of (8, 128), or equal the array dims;
- the fused kernel caps K (invokes per segment) at 8 and fixes its
  rung-0 frontier capacity F at 128 (one vreg row); its wide rungs
  double F up to a top rung of F_TOP_CAP configs, whose sort buffer,
  wide frontier and carries must fit the scoped VMEM budget.

Two layers:

- :func:`check_production` re-derives every ``spec_for`` tier the
  production bucket ladder can produce and budget-checks each
  (:func:`check_spec`); :func:`budget_table` renders the checked
  budgets as an artifact.
- :func:`scan_files` AST-scans ``pallas_call`` /
  ``PrefetchScalarGridSpec`` / ``BlockSpec`` sites (and ``spec_for``
  calls) for literally-bad configs, resolving module-level integer
  constants.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Tuple

from . import Finding, suppressed

SUBLANE, LANE = 8, 128
#: scalar-prefetch SMEM capacity in int32 words (~56 KB measured;
#: 2048x10 = 20480 words fails on v5e)
SMEM_PREFETCH_INT32 = 14336
#: approximate per-grid-step SMEM cost toward the 1 MB space
SMEM_STEP_BYTES = 500
SMEM_SPACE_BYTES = 1 << 20
#: fraction of the SMEM space the per-step cost may consume (the ~500
#: B/step figure is approximate; 0.7 rejects the measured-failing 2048
#: steps while accepting the measured-passing 1408)
SMEM_SAFETY = 0.7
#: longest grid measured to compile on v5e (2048 fails, 1408 passes)
MAX_GRID_STEPS = 1408
K_CAP = 8
F_CAP = 128
#: the top frontier rung: the first power of two past the largest
#: closure (2,264 configs) measured on the Jepsen register test's
#: concurrency-10 keys (256 keys x 2,000 ops)
F_TOP_CAP = 4096
#: v5e's default scoped VMEM limit (bytes) a kernel's scratch and
#: double-buffered blocks must fit without raising it
VMEM_BUDGET_BYTES = 16 << 20

#: the production shape-bucket ladder (mirrors
#: scripts/fuzz_pallas_seg.py; jaxpr_audit cross-checks the mirror)
PRODUCTION_BUCKETS: Tuple[Tuple[int, int], ...] = (
    (8, 32), (16, 64), (64, 64), (128, 64), (256, 8))


def _pallas_seg():
    from ..checker import pallas_seg
    return pallas_seg


def check_config(*, grid_steps: Optional[int] = None,
                 prefetch_int32: Optional[int] = None,
                 block: Optional[Tuple[int, int]] = None,
                 K: Optional[int] = None, F: Optional[int] = None,
                 f_top: Optional[int] = None,
                 vmem_bytes: Optional[int] = None,
                 where: str = "<config>", path: str = "<config>",
                 line: int = 0) -> List[Finding]:
    """Budget-check one kernel configuration; any field may be left
    None (unchecked). The golden tests drive this directly."""
    out: List[Finding] = []
    if grid_steps is not None:
        if grid_steps > MAX_GRID_STEPS:
            out.append(Finding(
                "pallas-grid-steps", path, line,
                f"{where}: {grid_steps}-step grid exceeds the measured "
                f"Mosaic compile bound ({MAX_GRID_STEPS}; 2048 fails "
                "with 'Exceeded smem capacity') — chunk the stream at "
                "1024"))
        elif grid_steps * SMEM_STEP_BYTES > \
                SMEM_SAFETY * SMEM_SPACE_BYTES:
            out.append(Finding(
                "pallas-grid-steps", path, line,
                f"{where}: {grid_steps} grid steps x ~{SMEM_STEP_BYTES}"
                f" B/step exceeds {SMEM_SAFETY:.0%} of the 1 MB SMEM "
                "space"))
    if prefetch_int32 is not None and \
            prefetch_int32 > SMEM_PREFETCH_INT32:
        out.append(Finding(
            "pallas-prefetch-smem", path, line,
            f"{where}: {prefetch_int32} int32 of scalar prefetch "
            f"exceeds the ~56 KB SMEM budget ({SMEM_PREFETCH_INT32} "
            "words; 2048x10 fails) — chunk the segment stream"))
    if block is not None:
        sub, lane = block[-2], block[-1]
        if lane % LANE != 0:
            out.append(Finding(
                "pallas-block-shape", path, line,
                f"{where}: block lane dim {lane} is not a multiple of "
                f"{LANE} — grid-step blocks need last-two dims "
                "divisible by (8,128) or equal to the array dims"))
        if not (sub % SUBLANE == 0 or SUBLANE % sub == 0):
            out.append(Finding(
                "pallas-block-shape", path, line,
                f"{where}: block sublane dim {sub} neither divides "
                f"nor is a multiple of {SUBLANE}"))
    if K is not None and K > K_CAP:
        out.append(Finding(
            "pallas-k-cap", path, line,
            f"{where}: K={K} exceeds the kernel cap of {K_CAP} "
            "invokes per segment (spec_for must gate on it)"))
    if F is not None and F != F_CAP:
        out.append(Finding(
            "pallas-f-cap", path, line,
            f"{where}: kernel frontier capacity must be F={F_CAP} "
            f"(one vreg row), got {F}"))
    if f_top is not None and (f_top > F_TOP_CAP or f_top < F_CAP
                              or f_top % F_CAP
                              or (f_top // F_CAP) & (f_top // F_CAP - 1)):
        out.append(Finding(
            "pallas-f-cap", path, line,
            f"{where}: the top frontier rung must double F={F_CAP} a "
            f"whole number of times up to at most {F_TOP_CAP}, got "
            f"{f_top}"))
    if vmem_bytes is not None and vmem_bytes > VMEM_BUDGET_BYTES:
        out.append(Finding(
            "pallas-vmem-budget", path, line,
            f"{where}: {vmem_bytes} B of VMEM scratch and blocks "
            f"exceed the {VMEM_BUDGET_BYTES >> 20} MiB scoped budget"))
    return out


def kernel_vmem_bytes(spec, b_pad: Optional[int] = None) -> int:
    """VMEM the fused kernel asks for at ``spec``: its scratch (rung-0
    buffer, wide frontier, the top rung's sort buffer) plus its blocks,
    double-buffered (key-word carries in and out, stat rows, results of
    ``b_pad`` histories, the table)."""
    PS = _pallas_seg()
    row = PS.LANES * 4
    b_pad = PS.MAX_STREAM_B if b_pad is None else b_pad
    scratch = spec.n_words * row * sum(PS.scratch_rows(spec))
    blocks = row * (2 * spec.n_words * PS.carry_rows(spec)
                    + 2 + 2 * b_pad + spec.table_rows_pad)
    return scratch + 2 * blocks


def check_spec(spec, *, where: str = "spec") -> List[Finding]:
    """Budget-check one :class:`SegKernelSpec` (prefetch width is
    ``2 + 2K`` int32 per segment, blocks are ``(rows, 128)``)."""
    PS = _pallas_seg()
    path = PS.__file__
    width = 2 + 2 * spec.K
    out = check_config(
        grid_steps=spec.chunk, prefetch_int32=spec.chunk * width,
        block=(PS.carry_rows(spec), PS.LANES), K=spec.K, F=PS.F,
        f_top=PS.F_TOP, vmem_bytes=kernel_vmem_bytes(spec),
        where=where, path=path, line=0)
    if spec.rows not in (PS.ROWS, 2 * PS.ROWS):
        out.append(Finding(
            "pallas-block-shape", path, 0,
            f"{where}: buffer rows {spec.rows} not in the (8,128)/"
            "(16,128) tier set"))
    if spec.n_words > 3:
        out.append(Finding(
            "pallas-key-words", path, 0,
            f"{where}: {spec.n_words} key words exceed the 3-word "
            "packed-key budget"))
    if spec.table_rows_pad * PS.LANES > PS.MAX_TABLE:
        out.append(Finding(
            "pallas-table-budget", path, 0,
            f"{where}: table buffer {spec.table_rows_pad}x{PS.LANES} "
            f"exceeds MAX_TABLE={PS.MAX_TABLE}"))
    return out


def production_tiers() -> List[Tuple[Tuple[int, int], int, int, object]]:
    """Every distinct ``spec_for`` spec reachable from the production
    bucket ladder x P (1..15) x K (1..8), with one witness
    (bucket, P, K) each."""
    PS = _pallas_seg()
    seen: Dict[object, Tuple[Tuple[int, int], int, int]] = {}
    for bucket in PRODUCTION_BUCKETS:
        for P in range(1, 16):
            for K in range(1, K_CAP + 1):
                spec = PS.spec_for(bucket[0], bucket[1], P, K)
                if spec is not None and spec not in seen:
                    seen[spec] = (bucket, P, K)
    return [(b, P, K, spec) for spec, (b, P, K) in seen.items()]


def check_production() -> List[Finding]:
    """Budget-check every production tier, plus the meta-gates: the
    budgets in this module must still be ENFORCED by ``spec_for``
    (K > 8 and P > 15 must be rejected, F must be 128)."""
    PS = _pallas_seg()
    path = PS.__file__
    out: List[Finding] = []
    for bucket, P, K, spec in production_tiers():
        out += check_spec(
            spec, where=f"spec_for({bucket[0]},{bucket[1]},P={P},K={K})")
    if PS.spec_for(8, 32, 3, K_CAP + 1) is not None:  # analysis: ignore[pallas-k-cap]
        out.append(Finding(
            "pallas-k-cap", path, 0,
            f"spec_for accepts K={K_CAP + 1}: the kernel serves at "
            f"most {K_CAP} invokes per segment"))
    if PS.spec_for(8, 32, 16, 1) is not None:
        out.append(Finding(
            "pallas-block-shape", path, 0,
            "spec_for accepts P=16: the (16,128) tier serves P <= 15"))
    out += check_config(F=PS.F, where="pallas_seg.F", path=path)
    out += check_config(f_top=PS.F_TOP, where="pallas_seg.F_TOP",
                        path=path)
    out += check_config(grid_steps=PS.CHUNK, where="pallas_seg.CHUNK",
                        path=path)
    return out


def budget_table() -> str:
    """The checked production budgets as a markdown artifact."""
    PS = _pallas_seg()
    rows = ["| bucket | P | K | rows | words | chunk | prefetch B "
            "| step-SMEM B | table rows | VMEM B |",
            "|---|---|---|---|---|---|---|---|---|---|"]
    for bucket, P, K, spec in sorted(
            production_tiers(),
            key=lambda t: (t[0], t[1], t[2])):
        width = 2 + 2 * spec.K
        rows.append(
            f"| {bucket[0]}x{bucket[1]} | {P} | {K} | {spec.rows} "
            f"| {spec.n_words} | {spec.chunk} "
            f"| {spec.chunk * width * 4} "
            f"| {spec.chunk * SMEM_STEP_BYTES} "
            f"| {spec.table_rows_pad} | {kernel_vmem_bytes(spec)} |")
    head = (f"# Pallas budget table (limits: prefetch <= "
            f"{SMEM_PREFETCH_INT32 * 4} B, grid <= {MAX_GRID_STEPS} "
            f"steps, K <= {K_CAP}, F = {F_CAP} rising to "
            f"{F_TOP_CAP}, VMEM <= {VMEM_BUDGET_BYTES} B)\n\n")
    return head + "\n".join(rows) + "\n"


# --- AST scan ---------------------------------------------------------------

def _module_consts(tree: ast.Module) -> Dict[str, int]:
    env: Dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            v = _fold(node.value, env)
            if v is not None:
                env[node.targets[0].id] = v
    return env


def _fold(node: ast.AST, env: Dict[str, int]) -> Optional[int]:
    """Constant-fold ints through names and + - * // arithmetic."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.BinOp):
        a, b = _fold(node.left, env), _fold(node.right, env)
        if a is None or b is None:
            return None
        if isinstance(node.op, ast.Add):
            return a + b
        if isinstance(node.op, ast.Sub):
            return a - b
        if isinstance(node.op, ast.Mult):
            return a * b
        if isinstance(node.op, ast.FloorDiv) and b != 0:
            return a // b
    return None


def _fold_tuple(node: ast.AST,
                env: Dict[str, int]) -> Optional[Tuple[int, ...]]:
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    vals = [_fold(e, env) for e in node.elts]
    if any(v is None for v in vals):
        return None
    return tuple(vals)   # type: ignore[arg-type]


def _call_name(node: ast.Call) -> str:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return ""


def scan_file(path: str, source: Optional[str] = None, *,
              apply_suppressions: bool = True) -> List[Finding]:
    """AST budget scan of one file."""
    if source is None:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return []           # lint reports syntax errors
    lines = source.splitlines()
    env = _module_consts(tree)
    # the prefetch-budget rule applies only to allocations in a scope
    # that actually builds a PrefetchScalarGridSpec — a big working
    # buffer elsewhere in the file is not scalar prefetch
    spec_ids = {id(n) for n in ast.walk(tree)
                if isinstance(n, ast.Call)
                and _call_name(n) == "PrefetchScalarGridSpec"}
    fn_ids = []
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_ids.append({id(x) for x in ast.walk(n)})
    prefetch_scopes = [ids for ids in fn_ids if ids & spec_ids]
    module_prefetch = bool(
        spec_ids - set().union(*fn_ids) if fn_ids else spec_ids)

    def in_prefetch_scope(call: ast.Call) -> bool:
        return module_prefetch or any(id(call) in ids
                                      for ids in prefetch_scopes)

    raw: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name in ("pallas_call", "PrefetchScalarGridSpec"):
            for kw in node.keywords:
                if kw.arg == "grid":
                    dims = _fold_tuple(kw.value, env)
                    if dims is None and kw.value is not None:
                        g = _fold(kw.value, env)
                        dims = (g,) if g is not None else None
                    if dims:
                        # grid steps run sequentially: the budget is
                        # the PRODUCT of the dims, not each dim alone
                        # (a (64, 64) grid is 4096 steps)
                        total = 1
                        for g in dims:
                            total *= g
                        raw += check_config(
                            grid_steps=total, where=name, path=path,
                            line=node.lineno)
        elif name == "BlockSpec" and node.args:
            shape = _fold_tuple(node.args[0], env)
            if shape is not None and len(shape) >= 2:
                raw += check_config(block=shape[-2:], where=name,
                                    path=path, line=node.lineno)
        elif name in ("zeros", "full", "empty", "ones") \
                and node.args and in_prefetch_scope(node):
            shape = _fold_tuple(node.args[0], env)
            if shape is not None and len(shape) >= 2:
                total = 1
                for d in shape:
                    total *= d
                raw += check_config(
                    prefetch_int32=total,
                    where=f"np.{name}{shape}", path=path,
                    line=node.lineno)
        elif name == "spec_for":
            k_node = None
            if len(node.args) >= 4:
                k_node = node.args[3]
            for kw in node.keywords:
                if kw.arg == "K":
                    k_node = kw.value
            k = _fold(k_node, env) if k_node is not None else None
            if k is not None:
                raw += check_config(K=k, where="spec_for", path=path,
                                    line=node.lineno)
    if not apply_suppressions:
        return raw
    return [f for f in raw if not suppressed(lines, f.line, f.rule)]


def scan_files(paths: Sequence[str]) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        if os.path.exists(p):
            out += scan_file(p)
    return out


from . import Pass, register_pass


def _repo_stage(ctx):
    return scan_files(ctx["files"]) + check_production()


register_pass(Pass(
    name="pallas-budget",
    scan_paths=scan_files,
    raw_file=lambda path, source: scan_file(
        path, source, apply_suppressions=False),
    repo_stage=_repo_stage,
))
