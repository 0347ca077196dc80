"""AST lint rules for the codified CLAUDE.md invariants.

Each rule carries its provenance in ``docs/static_analysis.md``; the
short story per rule id:

- ``jax-env-after-import`` — jax reads JAX/XLA env vars when it is
  imported, so env vars written after a jax import are read too late
  (the platform silently stays whatever it was). Use
  ``jax.config.update``.
- ``no-multiprocessing`` — the container exposes ONE CPU; a spawn pool
  measured 322 s -> 566 s on the 4096x generation (pure IPC overhead).
- ``hash-dedup`` — device-checker dedup must be EXACT
  (sort-adjacency); hash-fingerprint ordering lets colliding
  non-identical rows break adjacency and balloon the frontier.
- ``dup-cond-closure`` — inlining the same closure body under two
  branches of nested ``lax.cond`` makes XLA compile the body per
  branch path; CPU compile time explodes. Run the shared tier
  unconditionally and select with ONE cond.
- ``keyed-history-wrap`` — EDN ``[k v]`` values parse as plain tuples
  (a bare 2-tuple is a cas pair); modules that parse histories and
  check them must route keyed histories through
  ``independent.wrap_keyed_history``.
- ``nemesis-info-completion`` — nemesis completions must stay type
  ``info`` (PassThrough client) or ``history.complete`` rejects the
  history; an ok/fail completion would let the nemesis affect the
  model.
- ``per-item-dispatch`` — a loop dispatching ``check_device_batch`` /
  ``check_device`` (or ``closure_diag``/``cyclic_layers_device`` on
  the txn axis, or the shrink serial control ``check_candidate``) per
  item is round-trip-bound: each dispatch pays a dispatch+readback
  round-trip (measured 1.5k ops/s serial vs 93k streamed). Pack the
  items into ONE ``checker.batch.pack_batch`` / ``check_batch`` /
  ``shrink.verdicts.check_candidates`` call, or submit them to the
  ``comdb2_tpu.service`` verifier daemon, which coalesces callers
  into shared dispatches.
- ``per-op-host-loop`` — the pack/segment ingest path is columnar
  since round 6 (the per-op walk measured ``host_pack_s = 278.2``
  against ~70 s of device time at the 4096x bench shape); a ``for``
  loop over ``<x>.ops`` inside those modules reintroduces per-op
  Python on the hot path. Op objects are API-edge views only
  (counterexample decode, report rendering — suppression-listed).
- ``vmap-sharded-oracle`` — ``linear_jax.check_sharded`` (the vmap
  engine shard_mapped over a mesh) is a TEST ORACLE only: vmap lowers
  ~20x worse per lane than the key-packed encoding, so sharding it
  scales a pessimized program. Production mesh traffic rides the
  stream/keys sharded engines through ``check_batch``; any
  non-test call site routing serving traffic back onto the oracle is
  a finding (round 7 removed the last one).
- ``raw-clock-in-pipeline`` — ``time.time()``/``time.monotonic()``/
  ``time.perf_counter()`` read directly inside a dispatch-pipeline
  module (service/shrink/txn packages, checker ``linear.py``/
  ``batch.py``/``pallas_seg.py``). Timing there must go through
  ``comdb2_tpu.obs.trace`` (``monotonic()``, the span API): the
  per-request stage attribution (queue-wait / host-pack / device /
  finalize) only tiles the measured wall when every timestamp comes
  off ONE clock, and a raw ``time.time()`` (wall clock, steppable by
  the clock nemesis) silently corrupts device-time attribution.
  ``comdb2_tpu/obs`` itself and tests are exempt.
- ``host-numpy-checkpoint`` — session checkpoint/restore builders
  must be HOST numpy only (the round-11 ``_host_seg_carry`` rule
  generalized): a jnp-built checkpoint compiles infra programs
  OUTSIDE the declared inventory (scatter/pad per carry shape —
  one per session shape, per eviction), and eagerly round-trips
  host-device. ``np.asarray`` of a device array is a readback, never a
  compile; the restore upload rides the next delta dispatch's jit
  transfer. Scope: the ``stream`` package plus any
  "checkpoint"-named file (the fixture hook), functions whose name
  contains ``checkpoint``/``restore``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from . import Finding, suppressed

JAX_ENV_PREFIXES = ("JAX_", "XLA_")

CHECKER_ENTRY_NAMES = {"analysis", "check_history"}
PARSE_NAMES = {"parse_history", "parse_history_fast"}

#: single-batch device entry points that are round-trip-bound when
#: driven once per item from a host loop (``check_batch`` itself is
#: the batching API — a loop over BUCKETS of coalesced work is
#: legitimate, so only the per-history entries are flagged). The txn
#: closure engine's entries are covered too: one cycle check per
#: dependency graph must ride ``closure_diag_batch`` (or the service
#: txn kind), never a loop of ``closure_diag`` calls. The shrink
#: entry point ``check_candidate`` is covered for the same reason:
#: one verdict dispatch per ddmin candidate is the bug the shrink
#: subsystem exists to avoid — a round's candidates ride ONE
#: ``shrink.verdicts.check_candidates`` call per shape bucket.
PER_ITEM_DISPATCH_NAMES = {"check_device_batch", "check_device",
                           "closure_diag", "cyclic_layers_device",
                           "check_candidate"}

#: modules forming the columnar pack/segment ingest path — a per-op
#: ``for ... in <x>.ops`` loop there is the ``per-op-host-loop``
#: hazard (files whose basename contains "pack" are included so the
#: seeded fixture and future pack helpers are covered)
PACK_SEGMENT_MODULES = {"packed.py", "columnar.py",
                        "synth_columnar.py", "batch.py",
                        "linear_jax.py", "pallas_seg.py",
                        # the streaming delta ingest/segment path is
                        # columnar by the same contract (the session
                        # pays the pass PER APPEND, forever)
                        "ingest.py", "segment.py"}

#: package directories whose EVERY module is pack/segment scope —
#: checker/wl encodes whole batches into column planes (encoders,
#: delta builders, verdict decoders), so a ``.ops`` loop anywhere in
#: it is the same hazard
PACK_SEGMENT_DIRS = {"wl"}

#: the dispatch-pipeline scope of ``raw-clock-in-pipeline``: package
#: directories plus the checker dispatch modules (files whose
#: basename contains "dispatch" are included so the seeded fixture
#: and future dispatch helpers are covered); ``obs`` is the clock's
#: home and exempt
RAW_CLOCK_DIRS = {"service", "shrink", "txn", "stream", "wl"}
RAW_CLOCK_FILES = {"linear.py", "batch.py", "pallas_seg.py"}
RAW_CLOCK_FNS = {"time", "monotonic", "perf_counter"}

#: substrings naming the checkpoint/restore builders the
#: ``host-numpy-checkpoint`` rule audits (scope: the stream package
#: + "checkpoint"-named files, so the seeded fixture is covered)
CHECKPOINT_FN_PARTS = ("checkpoint", "restore")


def _name_of(node: ast.AST) -> str:
    """Trailing identifier of a Name/Attribute chain ('' otherwise)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _is_os_environ(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and _name_of(node.value) == "os")


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class _ModuleInfo(ast.NodeVisitor):
    """One traversal collecting everything the rules need."""

    def __init__(self) -> None:
        self.jax_import_line: Optional[int] = None   # module level
        self.imports_jax = False                     # anywhere
        self.mp_imports: List[Tuple[int, str]] = []
        self.hash_uses: List[int] = []
        self.env_writes: List[Tuple[int, str, bool]] = []  # ln, key, in_fn
        self.parse_calls: List[int] = []
        self.checker_calls: List[int] = []
        self.wrap_refs = 0
        self.nemesis_bad_type: List[Tuple[int, str]] = []
        self.cond_calls: List[ast.Call] = []
        self.func_defs: Dict[str, ast.AST] = {}
        self.loop_dispatch: List[Tuple[int, str]] = []
        self.ops_loops: List[int] = []
        self.vmap_oracle_calls: List[int] = []
        self.clock_calls: List[Tuple[int, str]] = []
        self.jax_aliases: set = set()      # `import jax [as x]`
        self.jnp_aliases: set = set()      # `import jax.numpy as jnp`
        self._time_modnames: set = set()   # `import time [as x]`
        self._time_aliases: set = set()    # `from time import ...`
        self._fn_depth = 0
        self._loop_depth = 0

    def _note_ops_iter(self, lineno: int, iter_node) -> None:
        """Record a loop whose iterated expression reaches a ``.ops``
        attribute (incl. wrapped forms like ``enumerate(p.ops)``)."""
        for sub in ast.walk(iter_node):
            if isinstance(sub, ast.Attribute) and sub.attr == "ops":
                self.ops_loops.append(lineno)
                return

    # -- imports -------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            top = a.name.split(".")[0]
            if top == "jax":
                self.imports_jax = True
                if self._fn_depth == 0 and self.jax_import_line is None:
                    self.jax_import_line = node.lineno
                if a.name == "jax":
                    self.jax_aliases.add(a.asname or "jax")
                elif a.name == "jax.numpy" and a.asname:
                    self.jnp_aliases.add(a.asname)
                elif not a.asname:
                    # `import jax.numpy` (no asname) binds the NAME
                    # `jax`: `jax.numpy.zeros(...)` must resolve
                    # through the jax root like any other submodule
                    self.jax_aliases.add("jax")
            if top == "multiprocessing":
                self.mp_imports.append((node.lineno, a.name))
            if a.name == "time":
                self._time_modnames.add(a.asname or "time")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        top = (node.module or "").split(".")[0]
        if top == "jax":
            self.imports_jax = True
            if self._fn_depth == 0 and self.jax_import_line is None:
                self.jax_import_line = node.lineno
            if node.module == "jax":
                for a in node.names:
                    if a.name == "numpy":
                        self.jnp_aliases.add(a.asname or "numpy")
            elif node.module == "jax.numpy":
                # `from jax.numpy import zeros` — any imported name
                # is a device-op constructor inside a checkpoint
                for a in node.names:
                    self.jnp_aliases.add(a.asname or a.name)
        if top == "multiprocessing":
            self.mp_imports.append((node.lineno, node.module or top))
        if top == "concurrent":
            for a in node.names:
                if a.name == "ProcessPoolExecutor":
                    self.mp_imports.append((node.lineno, a.name))
        if node.module == "time":
            for a in node.names:
                if a.name in RAW_CLOCK_FNS:
                    self._time_aliases.add(a.asname or a.name)
        self.generic_visit(node)

    # -- defs / scoping ------------------------------------------------

    def _visit_fn(self, node) -> None:
        self.func_defs.setdefault(node.name, node)
        self._fn_depth += 1
        self.generic_visit(node)
        self._fn_depth -= 1

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _visit_loop(self, node) -> None:
        it = getattr(node, "iter", None)        # For / AsyncFor
        if it is not None:
            self._note_ops_iter(node.lineno, it)
        for gen in getattr(node, "generators", ()):  # comprehensions
            self._note_ops_iter(node.lineno, gen.iter)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop
    visit_ListComp = _visit_loop
    visit_SetComp = _visit_loop
    visit_DictComp = _visit_loop
    visit_GeneratorExp = _visit_loop

    # -- expressions ---------------------------------------------------

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (node.attr == "ProcessPoolExecutor"
                and _name_of(node.value) == "futures"):
            self.mp_imports.append((node.lineno, "ProcessPoolExecutor"))
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        if node.id == "wrap_keyed_history":
            self.wrap_refs += 1
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if (isinstance(t, ast.Subscript)
                    and _is_os_environ(t.value)):
                key = _const_str(t.slice)
                if key and key.startswith(JAX_ENV_PREFIXES):
                    self.env_writes.append(
                        (node.lineno, key, self._fn_depth > 0))
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        name = _name_of(fn)
        if isinstance(fn, ast.Name) and name == "hash":
            self.hash_uses.append(node.lineno)
        if name == "setdefault" and isinstance(fn, ast.Attribute) \
                and _is_os_environ(fn.value) and node.args:
            key = _const_str(node.args[0])
            if key and key.startswith(JAX_ENV_PREFIXES):
                self.env_writes.append(
                    (node.lineno, key, self._fn_depth > 0))
        if name in PER_ITEM_DISPATCH_NAMES and self._loop_depth > 0:
            self.loop_dispatch.append((node.lineno, name))
        if isinstance(fn, ast.Attribute) and fn.attr in RAW_CLOCK_FNS \
                and _name_of(fn.value) in self._time_modnames:
            self.clock_calls.append(
                (node.lineno, f"{_name_of(fn.value)}.{fn.attr}"))
        elif isinstance(fn, ast.Name) and fn.id in self._time_aliases:
            self.clock_calls.append((node.lineno, fn.id))
        if name == "check_sharded":
            self.vmap_oracle_calls.append(node.lineno)
        if name in PARSE_NAMES:
            self.parse_calls.append(node.lineno)
        if name in CHECKER_ENTRY_NAMES:
            self.checker_calls.append(node.lineno)
        if name == "wrap_keyed_history":
            self.wrap_refs += 1
        if name in ("cond", "switch") \
                and _name_of(getattr(fn, "value", None)) in ("lax",
                                                            "jax"):
            self.cond_calls.append(node)
        # nemesis completion types: Op(..., type="ok"/"fail"),
        # op.with_(type=...), and {**op, "type": "ok"} dict displays
        # are caught in _nemesis_scan (dict displays aren't calls)
        if name in ("Op", "with_"):
            for kw in node.keywords:
                if kw.arg == "type":
                    v = _const_str(kw.value)
                    if v in ("ok", "fail"):
                        self.nemesis_bad_type.append((node.lineno, v))
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict) -> None:
        for k, v in zip(node.keys, node.values):
            if k is not None and _const_str(k) == "type":
                val = _const_str(v)
                if val in ("ok", "fail"):
                    self.nemesis_bad_type.append((node.lineno, val))
        self.generic_visit(node)


def _hash_args(node: ast.Call) -> List[int]:
    """Lines where builtin ``hash`` is passed as a sort key
    (``key=hash``) — dedup by hash without even a call."""
    out = []
    for kw in node.keywords:
        if kw.arg == "key" and isinstance(kw.value, ast.Name) \
                and kw.value.id == "hash":
            out.append(node.lineno)
    return out


def _branches(call: ast.Call) -> List[ast.AST]:
    """Branch callables of a lax.cond/lax.switch call node."""
    name = _name_of(call.func)
    if name == "cond":
        return list(call.args[1:3])
    if name == "switch" and len(call.args) >= 2 \
            and isinstance(call.args[1], (ast.List, ast.Tuple)):
        return list(call.args[1].elts)
    return []


def _branch_key(branch: ast.AST,
                defs: Dict[str, ast.AST]) -> Optional[str]:
    """Structural fingerprint of a branch body; None for trivial
    branches (no call in the body — pass-through lambdas legitimately
    repeat)."""
    body: Optional[ast.AST] = None
    if isinstance(branch, ast.Lambda):
        body = branch.body
    elif isinstance(branch, ast.Name) and branch.id in defs:
        body = ast.Module(body=defs[branch.id].body, type_ignores=[])
    if body is None:
        return None
    if not any(isinstance(n, ast.Call) for n in ast.walk(body)):
        return None
    return ast.dump(body)


def _cond_subtree(call: ast.Call,
                  defs: Dict[str, ast.AST]) -> set:
    """Node-identity set of the cond call's subtree, with Name
    branches resolved to their local function definitions (so a cond
    inside a named branch counts as nested under this cond)."""
    nodes = set(map(id, ast.walk(call)))
    for br in _branches(call):
        if isinstance(br, ast.Name) and br.id in defs:
            nodes |= set(map(id, ast.walk(defs[br.id])))
    return nodes


def _dup_cond_findings(info: _ModuleInfo, path: str,
                       lines) -> List[Finding]:
    conds = info.cond_calls
    out: List[Finding] = []
    keyed = []
    for c in conds:
        keys = [(_branch_key(b, info.func_defs), b) for b in
                _branches(c)]
        keyed.append([k for k, _ in keys])
        # same non-trivial body twice under ONE cond
        seen = set()
        for k, _ in keys:
            if k is None:
                continue
            if k in seen:
                out.append(Finding(
                    "dup-cond-closure", path, c.lineno,
                    "identical closure body under two branches of one "
                    "lax.cond — hoist it and select inputs instead"))
            seen.add(k)
    subtrees = [_cond_subtree(c, info.func_defs) for c in conds]
    for i, ci in enumerate(conds):
        for j, cj in enumerate(conds):
            if i == j or id(cj) not in subtrees[i]:
                continue
            dup = set(k for k in keyed[i] if k) \
                & set(k for k in keyed[j] if k)
            if dup:
                out.append(Finding(
                    "dup-cond-closure", path, cj.lineno,
                    f"closure body duplicated between nested lax.cond "
                    f"branches (outer at line {ci.lineno}): XLA "
                    "compiles it once per branch path — run the "
                    "shared tier unconditionally, select with ONE "
                    "cond"))
    return out


def _checkpoint_findings(tree: ast.AST, info: _ModuleInfo,
                         path: str) -> List[Finding]:
    """``host-numpy-checkpoint``: device ops (jnp/jax attribute
    chains, or names imported from jax.numpy) inside a function whose
    name marks it a checkpoint/restore builder."""
    bases = info.jax_aliases | info.jnp_aliases
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        name = node.name.lower()
        if not any(p in name for p in CHECKPOINT_FN_PARTS):
            continue
        hits = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute):
                root = sub
                while isinstance(root.value, ast.Attribute):
                    root = root.value
                if isinstance(root.value, ast.Name) \
                        and root.value.id in bases:
                    hits.add(sub.lineno)
            elif isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Name) \
                    and sub.func.id in info.jnp_aliases:
                hits.add(sub.lineno)
        for ln in sorted(hits):
            out.append(Finding(
                "host-numpy-checkpoint", path, ln,
                f"jax/jnp op inside {node.name}() — checkpoint/"
                "restore builders must be HOST numpy only: a "
                "jnp-built snapshot compiles infra programs outside "
                "the declared inventory (one per carry shape, per "
                "eviction) and eagerly round-trips host-device; "
                "np.asarray reads back, the next delta dispatch's "
                "jit transfer uploads"))
    return out


def lint_file(path: str, source: Optional[str] = None, *,
              apply_suppressions: bool = True) -> List[Finding]:
    """All lint findings for one file (suppressions applied unless
    ``apply_suppressions=False`` — the stale-suppression audit needs
    the raw findings to decide which markers still earn their keep)."""
    if source is None:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding("syntax-error", path, e.lineno or 0, str(e))]
    lines = source.splitlines()
    info = _ModuleInfo()
    info.visit(tree)

    raw: List[Finding] = []

    for ln, key, in_fn in info.env_writes:
        after_import = (info.jax_import_line is not None
                        and ln > info.jax_import_line)
        if in_fn or after_import:
            raw.append(Finding(
                "jax-env-after-import", path, ln,
                f"os.environ[{key!r}] written after jax import — jax "
                "reads env only at import; use jax.config.update"))

    for ln, what in info.mp_imports:
        raw.append(Finding(
            "no-multiprocessing", path, ln,
            f"{what}: the container exposes ONE CPU — a spawn pool is "
            "pure IPC overhead (measured 322 s -> 566 s); keep "
            "host-side work single-process"))

    if info.imports_jax:
        hash_lines = list(info.hash_uses)
        for c in ast.walk(tree):
            if isinstance(c, ast.Call):
                hash_lines += _hash_args(c)
        for ln in sorted(set(hash_lines)):
            raw.append(Finding(
                "hash-dedup", path, ln,
                "builtin hash() in a jax engine module — device "
                "dedup must be EXACT (sort-adjacency), never "
                "hash-fingerprint ordering"))
        raw += _dup_cond_findings(info, path, lines)

    parts = path.replace("\\", "/").split("/")
    base = parts[-1]
    # scoped to production code: tests parse histories THEY generated
    # (known non-keyed); the hazard is entry points fed arbitrary EDN.
    # Seeded fixtures under tests/fixtures/ are NOT exempt — they
    # exist to trip the rules
    in_tests = (base.startswith("test_")
                or ("tests" in parts and "fixtures" not in parts))
    if info.parse_calls and info.checker_calls and not info.wrap_refs \
            and not in_tests:
        raw.append(Finding(
            "keyed-history-wrap", path, info.parse_calls[0],
            "module parses EDN histories and runs a checker without "
            "referencing independent.wrap_keyed_history — EDN [k v] "
            "values parse as plain tuples (a bare 2-tuple is a cas "
            "pair)"))

    if not in_tests and base != "linear_jax.py":
        # check_sharded (the vmap-sharded oracle) may be DEFINED in
        # linear_jax and CALLED from tests; everything else routing
        # mesh traffic onto it is serving a 20x-pessimized engine
        for ln in info.vmap_oracle_calls:
            raw.append(Finding(
                "vmap-sharded-oracle", path, ln,
                "check_sharded is a test oracle — vmap lowers ~20x "
                "worse per lane, so sharding it scales a pessimized "
                "program; route mesh traffic through checker.batch."
                "check_batch (stream/keys sharded engines)"))

    if not in_tests:
        # tests legitimately compare per-item vs batched results; the
        # hazard is production paths serving traffic one dispatch per
        # history (each pays a dispatch round-trip)
        for ln, fname in info.loop_dispatch:
            raw.append(Finding(
                "per-item-dispatch", path, ln,
                f"{fname} dispatched inside a loop — per-item device "
                "calls are round-trip-bound (measured 1.5k vs 93k "
                "ops/s); pack the items through checker.batch."
                "pack_batch/check_batch (shrink candidates: shrink."
                "verdicts.check_candidates) or submit them to the "
                "comdb2_tpu.service verifier daemon"))

    # dispatch-pipeline scope: the service/shrink/txn packages, the
    # checker dispatch modules, and any "dispatch"-named file (the
    # fixture hook); obs owns the clock, tests drive deadlines with
    # whatever clock they like
    in_pipeline = (not in_tests and "obs" not in parts
                   and ((set(parts) & RAW_CLOCK_DIRS
                         and "comdb2_tpu" in parts)
                        or base in RAW_CLOCK_FILES
                        or "dispatch" in base))
    if in_pipeline:
        for ln, what in info.clock_calls:
            raw.append(Finding(
                "raw-clock-in-pipeline", path, ln,
                f"{what}() read directly in a dispatch-pipeline "
                "module — route timing through comdb2_tpu.obs.trace "
                "(monotonic()/span()): stage sums only tile the "
                "measured wall when every timestamp shares ONE "
                "monotonic clock (docs/observability.md)"))

    # checkpoint/restore scope: the stream package (where the session
    # snapshot path lives) + any "checkpoint"-named file (fixture
    # hook); tests may build whatever debug snapshots they like
    if not in_tests and ("checkpoint" in base
                         or ("stream" in parts
                             and "comdb2_tpu" in parts)):
        raw += _checkpoint_findings(tree, info, path)

    if (base in PACK_SEGMENT_MODULES or "pack" in base
            or (not in_tests and set(parts) & PACK_SEGMENT_DIRS
                and "comdb2_tpu" in parts)):
        for ln in info.ops_loops:
            raw.append(Finding(
                "per-op-host-loop", path, ln,
                "for-loop over .ops inside the pack/segment ingest "
                "path — the packer is columnar (per-op Python "
                "measured host_pack_s=278.2 vs ~70 s device at the "
                "4096x shape); keep Op objects an API-edge view and "
                "work on the struct-of-arrays columns"))

    if "nemesis" in base:
        for ln, val in info.nemesis_bad_type:
            raw.append(Finding(
                "nemesis-info-completion", path, ln,
                f"nemesis completion typed {val!r} — nemesis ops must "
                "stay :info (PassThrough client) or history.complete "
                "rejects the history"))

    if not apply_suppressions:
        return raw
    return [f for f in raw if not suppressed(lines, f.line, f.rule)]


def lint_files(paths) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        out += lint_file(p)
    return out


from . import Pass, register_pass

register_pass(Pass(
    name="lint",
    scan_paths=lint_files,
    raw_file=lambda path, source: lint_file(
        path, source, apply_suppressions=False),
))
