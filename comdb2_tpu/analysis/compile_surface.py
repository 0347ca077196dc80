"""Pass 4 — the compile-surface prover.

The framework's serving discipline is that every jit/Pallas-visible
shape comes from a CLOSED, pow2-bucketed program set: the service
buckets admission (:mod:`comdb2_tpu.service.bucketing`), ``check_batch``
floors its segment/table axes, the shrink minimizer groups candidates
into pow2 kept-op buckets, the txn closure pads N pow2, and the fused
kernel compiles one Mosaic program per :class:`SegKernelSpec`. That
discipline existed only as prose and convention; the known failure mode
(per-seed shapes compiling one program per seed until LLVM OOMs) is
exactly what the multi-chip and continuous-batching roadmap items would
multiply. This pass turns "the program set seemed closed" into a
machine-checked statement, in three parts:

- :func:`static_inventory` — walk the DECLARED ladders (service bucket
  axes from :class:`ServiceLimits`, the ``check_batch`` shape floors,
  shrink pow2 kept-op buckets, txn pow2-N buckets, every ``spec_for``
  tier reachable from the production bucket ladder) and enumerate the
  finite set of compilable programs per dispatch site.
- :func:`trace_witnesses` — abstractly evaluate one witness rung per
  site through the REAL entry point via ``jax.eval_shape`` over
  ``ShapeDtypeStruct`` ladders (builds the jaxpr only — no XLA
  compile, no device): a ladder whose shapes no longer trace is a
  finding, not a 40 s compile failure.
- :func:`scan_files` — the ``unbucketed-dispatch-site`` rule: an AST
  scan of the batch/serving dispatch sites whose shape arguments must
  come from a declared ladder. INTERPROCEDURAL: a shape argument that
  is a function parameter is chased through the call graph to every
  call site, so a raw ``memo.n_states`` laundered through a helper is
  still caught. Only PROVABLY-raw values are flagged (``len(...)``,
  ``.shape[...]``, raw memo-count attributes, non-pow2 literals);
  values whose provenance is out of AST reach stay silent — the
  runtime guard (:mod:`comdb2_tpu.utils.compile_guard`) is the
  backstop for those.

``render_programs`` emits the inventory as the checked-in
``PROGRAMS.md`` artifact (same drift contract as the budget table:
tier-1 regenerates it and any diff is a failure). The runtime half —
observed-compile capture and the subset assertion — lives in
:mod:`comdb2_tpu.utils.compile_guard`; :meth:`Inventory.offenders`
is the bridge between the two.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from . import Finding, suppressed

def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _pow2_count(lo: int, hi: int) -> int:
    return hi.bit_length() - lo.bit_length() + 1


# --- axis / site model ------------------------------------------------------

@dataclass(frozen=True)
class Axis:
    """One declared integer axis of a traced argument shape."""

    name: str
    kind: str                 # pow2 | enum | linear
    lo: int = 1
    hi: int = 1 << 20
    values: Tuple[int, ...] = ()

    def admits(self, v: int) -> bool:
        if self.kind == "enum":
            return v in self.values
        if self.kind == "pow2":
            return _is_pow2(v) and self.lo <= v <= self.hi
        if self.kind == "linear":
            return self.lo <= v <= self.hi
        raise ValueError(self.kind)

    @property
    def cardinality(self) -> Optional[int]:
        """Distinct admitted values, or None for linear axes (those
        compile one program per value BY DESIGN — chunked scans)."""
        if self.kind == "enum":
            return len(set(self.values))
        if self.kind == "pow2":
            return _pow2_count(self.lo, self.hi)
        return None

    def describe(self) -> str:
        if self.kind == "enum":
            return "{" + ",".join(str(v)
                                  for v in sorted(set(self.values))) + "}"
        if self.kind == "pow2":
            return f"pow2 {self.lo}..{self.hi}"
        return f"1..{self.hi} (linear: one program per value)"


@dataclass(frozen=True)
class Site:
    """One dispatch site: the jit names it compiles under plus the
    declared shape templates its programs are drawn from.

    ``templates``: tuple of argument-list templates; each template is
    a tuple of per-argument Axis tuples (scalar argument = empty
    tuple). A record matches when ANY template fits rank-for-rank and
    every dim is admitted. ``open_site=True`` matches any shapes —
    per-item programs by design (the single-history driver).
    """

    key: str
    jit_names: Tuple[str, ...]
    note: str
    templates: Tuple[Tuple[Tuple[Axis, ...], ...], ...] = ()
    open_site: bool = False    # per-item shapes by design (driver)
    axes_doc: Tuple[Axis, ...] = ()   # the distinct axes, for the doc
    bound_note: str = ""

    def matches(self, shapes: Sequence[Tuple[int, ...]]) -> bool:
        if self.open_site:
            return True
        for tmpl in self.templates:
            if len(tmpl) != len(shapes):
                continue
            ok = True
            for axes, shape in zip(tmpl, shapes):
                if len(axes) != len(shape) or \
                        any(not ax.admits(d)
                            for ax, d in zip(axes, shape)):
                    ok = False
                    break
            if ok:
                return True
        return False

    def program_bound(self) -> str:
        """Human-readable bound on distinct programs this site can
        compile (linear axes annotated, not multiplied in)."""
        if self.bound_note:
            return self.bound_note
        total = 1
        linear = []
        for ax in self.axes_doc:
            c = ax.cardinality
            if c is None:
                linear.append(ax.name)
            else:
                total *= c
        out = f"<= {total}"
        if linear:
            out += " x one per value of " + ",".join(linear)
        return out


@dataclass(frozen=True)
class Inventory:
    """The full static program inventory + the infra allowlist.

    ``infra_names``: jit names of O(1)-shaped host-glue programs
    (scalar converts, iota builders) that ride along with any
    workload; they are name-allowlisted, not shape-constrained — the
    prover's guarantee covers the ENGINE surface."""

    sites: Tuple[Site, ...]
    infra_names: frozenset

    def site_for(self, name: str) -> Optional[Site]:
        for s in self.sites:
            if name in s.jit_names:
                return s
        return None

    def matches(self, record) -> bool:
        """record: any object with ``.name`` and ``.shapes``."""
        site = self.site_for(record.name)
        if site is not None:
            return site.matches(record.shapes)
        return record.name in self.infra_names

    def offenders(self, records) -> list:
        """The records OUTSIDE the declared compile surface."""
        return [r for r in records if not self.matches(r)]


# --- the declared ladders ---------------------------------------------------

def _ladders() -> dict:
    """Every closed value set, derived from the DECLARED constants —
    never from observed traffic (the whole point is that traffic can't
    widen the set)."""
    from ..service.bucketing import ServiceLimits
    from ..service.sharding import MAX_SHARDS
    from ..shrink.verdicts import MAX_BATCH, MIN_BUCKET
    from ..txn.edges import TXN_N_FLOOR
    from ..stream import engine as stream_engine
    from ..utils import next_pow2
    from .pallas_budget import PRODUCTION_BUCKETS
    from .pallas_budget import production_tiers

    lim = ServiceLimits()
    specs = [spec for _, _, _, spec in production_tiers()]
    from ..checker import mxu
    from ..checker import pallas_seg
    from ..checker.linear_jax import make_pack_plan
    from ..checker.wl import batch as wl_batch

    # every PackPlan word count reachable inside the MXU table caps —
    # the chunk form's carry exposes one (F,)-shaped word column per
    # plan word, so the template set enumerates W
    mxu_words = sorted({
        plan.n_words
        for ns in (1 << i for i in range(mxu.S_CAP.bit_length()))
        for nt in (1 << i for i in range(mxu.T_CAP.bit_length()))
        for P in range(1, mxu.MAX_P + 1)
        for plan in (make_pack_plan(ns, nt, P),)
        if plan is not None})
    return {
        "limits": lim,
        "fuzz_buckets": tuple(PRODUCTION_BUCKETS),
        "specs": specs,
        "mesh_D": (1, MAX_SHARDS),
        "kernel_chunks": tuple(sorted({s.chunk for s in specs})),
        "kernel_widths": tuple(sorted({2 + 2 * s.K for s in specs})),
        # key-word carry rows: the (8,128)/(16,128) rung-0 buffer
        # plus the rows a wide-rung frontier crosses chunk calls in
        "kernel_rows": tuple(sorted({pallas_seg.carry_rows(s)
                                     for s in specs})),
        "kernel_rungs": tuple(pallas_seg.F << r for r in
                              range(pallas_seg.WIDE_RUNGS + 1)),
        "kernel_table_rows": tuple(sorted({s.table_rows_pad
                                           for s in specs})),
        "kernel_words": tuple(sorted({s.n_words for s in specs})),
        "service_n_pad": (16, next_pow2(lim.max_ops)),
        "service_S": (8, next_pow2(lim.max_segments)),
        "service_K": (2, next_pow2(lim.max_invokes_per_seg)),
        "service_P": (2, next_pow2(lim.max_processes)),
        "txn_N": (TXN_N_FLOOR, 1 << 16),
        "shrink_bucket": (MIN_BUCKET, next_pow2(lim.max_ops)),
        "shrink_B": (1, MAX_BATCH),
        "batch_B": (1, 1 << 12),
        "memo_dim": (1, 1 << 20),
        "mxu_table": (mxu.S_CAP, mxu.T_CAP),
        "mxu_F": tuple(mxu.CAPACITIES),
        "mxu_chunk": (64, mxu.CHUNK),
        "mxu_P": (mxu.MIN_P, mxu.MAX_P),
        "mxu_words": tuple(mxu_words),
        "stream_delta": tuple(stream_engine.DELTA_PADS),
        "stream_F": tuple(stream_engine.STREAM_CAPACITIES),
        # session slot widths: even-bucketed like the driver, capped
        # by the MXU crossover ceiling (wider P has no engine)
        "stream_P": tuple(range(2, mxu.MAX_P + 1, 2)),
        # megabatch session-lane rungs (fused advance: N sessions,
        # one program) and the kernel rung's small-delta chunk rungs
        "stream_B": tuple(stream_engine.MEGABATCH_LANES),
        "stream_small_chunks": tuple(pallas_seg.STREAM_CHUNKS),
        # workload-family ladders (checker/wl/batch.py — the wl-bank/
        # wl-sets/wl-dirty sites; docs/workloads.md)
        "wl_batch": tuple(wl_batch.WL_BATCH),
        "wl_reads": tuple(wl_batch.WL_READS),
        "wl_accounts": tuple(wl_batch.WL_ACCOUNTS),
        "wl_snaps": tuple(wl_batch.WL_SNAPS),
        "wl_elems": tuple(wl_batch.WL_ELEMS),
        "wl_nodes": tuple(wl_batch.WL_NODES),
        "wl_values": tuple(wl_batch.WL_VALUES),
        "wl_delta": tuple(wl_batch.WL_DELTA_PADS),
    }


#: host-glue jit names observed on the engine workloads: scalar dtype
#: converts and tiny index builders XLA compiles once per (dtype,
#: rank-0/1 shape). Name-allowlisted (shapes unconstrained) — the
#: closure guarantee covers the engine sites above. ONLY jax-internal
#: primitive-wrapper names belong here: a generic user-function name
#: (e.g. "fn", "run" without its site) would exempt arbitrary engine
#: code from the guarantee.
INFRA_NAMES = frozenset({
    "convert_element_type", "_threefry_seed", "_uint32",
    "iota", "arange", "broadcast_in_dim", "reshape", "concatenate",
    "_power", "true_divide", "floor_divide", "remainder",
    # sharded-array readback glue: jax fetches a mesh-sharded output
    # through one _multi_slice program per (shape, sharding) — pure
    # host-transfer plumbing, shapes follow the (already constrained)
    # engine outputs
    "_multi_slice",
})


def static_inventory() -> Inventory:
    """Build the declared compile surface (pure host work — imports
    the ladder constants, never jax)."""
    L = _ladders()
    lane = Axis("lane", "enum", values=(128,))
    one = Axis("one", "enum", values=(1,))
    four = Axis("planes", "enum", values=(4,))

    memo = Axis("n_states/n_transitions", "pow2", *L["memo_dim"])
    S = Axis("S", "pow2", 1, L["service_S"][1] << 4)
    K = Axis("K", "pow2", 1, 8)
    B = Axis("B", "pow2", *L["batch_B"])

    xla_batch_seg = (
        (memo, memo), (S, B, K), (S, B, K), (S, B), (S,))

    n_chunks = Axis("n_chunks", "linear", 1, 1 << 16)
    chunk = Axis("chunk", "enum", values=L["kernel_chunks"] + (16,))
    width = Axis("2+2K", "enum", values=L["kernel_widths"])
    rows = Axis("rows", "enum", values=L["kernel_rows"])
    table_rows = Axis("table_rows", "enum",
                      values=L["kernel_table_rows"])
    b_pad = Axis("b_pad", "pow2", 8, 2048)
    mesh_D = Axis("D", "pow2", *L["mesh_D"])
    off2 = Axis("off", "enum", values=(2,))
    res8 = Axis("res_rows", "enum", values=(8,))
    run_templates = []
    chunk_templates = []
    run_sharded_templates = []
    reset_templates = []
    for W in L["kernel_words"]:
        run_templates.append(
            ((n_chunks, chunk, width),)
            + ((rows, lane),) * W
            + ((one, lane), (b_pad, lane), (table_rows, lane), ()))
        # one chunk call as its own program (pallas_seg._chunk_jit):
        # a spec chunk + (offset, nt), the (ws, stat, res) carry with
        # the single-history 8-row results buffer, the packed table
        chunk_templates.append(
            ((chunk, width), (off2,))
            + ((rows, lane),) * W
            + ((one, lane), (res8, lane), (table_rows, lane)))
        # the shard_map form: every per-shard tensor gains the leading
        # mesh axis; per-shard shapes are the bucketed shapes divided
        # by D (global / D — both pow2, so the division stays on the
        # ladder). Table + stride stay replicated.
        run_sharded_templates.append(
            ((mesh_D, n_chunks, chunk, width),)
            + ((mesh_D, rows, lane),) * W
            + ((mesh_D, one, lane), (mesh_D, b_pad, lane),
               (table_rows, lane), ()))
        # the donated-carry reset (pallas_seg._reset_fn): re-fills a
        # recycled (ws, stat) carry set on device — inputs are the
        # scan's carry shapes, one program per (spec word/row class,
        # b_pad) already admitted by the run templates above
        reset_templates.append(
            ((rows, lane),) * W + ((one, lane),))

    N = Axis("N", "pow2", *L["txn_N"])
    N8 = Axis("N/8", "pow2", L["txn_N"][0] // 8, L["txn_N"][1] // 8)
    txn_B = Axis("B", "pow2", 1, 1 << 12)

    mxu_S = Axis("mxu_n_states", "pow2", 1, L["mxu_table"][0])
    mxu_T = Axis("mxu_n_transitions", "pow2", 1, L["mxu_table"][1])
    mxu_F = Axis("F", "enum", values=L["mxu_F"])
    mxu_chunk_ax = Axis("mxu_chunk", "pow2", *L["mxu_chunk"])
    mxu_words_ax = Axis("n_words", "enum", values=L["mxu_words"])
    # a genuinely concurrent wide-P wave puts up to P invokes in one
    # segment, so the engine's K axis runs to MAX_P (the kernel's
    # K <= 8 cap is a Mosaic budget, not an XLA/MXU one)
    mxu_K = Axis("mxu_K", "pow2", 1, L["mxu_P"][1])
    # batch form: succ + (S, B, K) segment tensors, like keys
    mxu_batch_tmpl = ((mxu_S, mxu_T), (S, B, mxu_K), (S, B, mxu_K),
                      (S, B), (S,))
    # single-history form (the driver's non-chunked path)
    mxu_single_tmpl = ((mxu_S, mxu_T), (S, mxu_K), (S, mxu_K), (S,),
                       (S,))
    # chunk form: args + seg_offset scalar + the B=1 carry — n_words
    # (F,) packed word columns, (F,) valid, then n_b/status/fail (1,)
    mxu_chunk_tmpls = []
    for W in L["mxu_words"]:
        mxu_chunk_tmpls.append(
            ((mxu_S, mxu_T), (mxu_chunk_ax, mxu_K),
             (mxu_chunk_ax, mxu_K), (mxu_chunk_ax,), (mxu_chunk_ax,),
             ())
            + ((mxu_F,),) * W
            + ((mxu_F,), (one,), (one,), (one,)))

    # stream-delta site (docs/streaming.md): the session append's
    # delta tensors + the resident carry. The carry's (F,) / (F, P)
    # planes ride the STREAM_CAPACITIES x even-P ladders; scalars
    # (seg_offset, count, status, fail) are shape ()
    stream_delta_ax = Axis("delta_pad", "enum",
                           values=L["stream_delta"])
    stream_K = Axis("stream_K", "pow2", 1, L["mxu_P"][1])
    stream_F_ax = Axis("stream_F", "enum", values=L["stream_F"])
    stream_P_ax = Axis("stream_P", "enum", values=L["stream_P"])
    stream_templates = [
        ((memo, memo), (stream_delta_ax, stream_K),
         (stream_delta_ax, stream_K), (stream_delta_ax,),
         (stream_delta_ax,), (),
         (stream_F_ax,), (stream_F_ax, stream_P_ax), (stream_F_ax,),
         (), (), ()),
    ]
    # megabatch session-lane ladder (round 13): N same-shape-class
    # sessions advance in ONE program — B-tuples of per-lane memo
    # tables/carries plus lane-major delta tensors
    stream_B_ax = Axis("session_B", "enum", values=L["stream_B"])
    # the kernel rung's chunk axis gains the small-delta rungs
    # (pallas_seg.STREAM_CHUNKS via delta_spec) — stream jit names
    # only; pallas-stream-scan keeps the tight spec_for ladder
    stream_chunk_ax = Axis(
        "stream_chunk", "enum",
        values=tuple(sorted(set(L["kernel_chunks"]) | {16}
                            | set(L["stream_small_chunks"]))))
    stream_mb_templates = []
    for Bn in L["stream_B"]:
        stream_mb_templates.append(
            ((memo, memo),) * Bn
            + ((stream_B_ax, stream_delta_ax, stream_K),
               (stream_B_ax, stream_delta_ax, stream_K),
               (stream_B_ax, stream_delta_ax),
               (stream_B_ax, stream_delta_ax), (stream_B_ax,))
            + ((stream_F_ax,), (stream_F_ax, stream_P_ax),
               (stream_F_ax,), (), (), ()) * Bn)
    # MXU-rung megabatch: same lane-major deltas (pads floored to the
    # MXU chunk ladder) + B-tuples of the B=1 chunk-form carry
    mxu_mb_templates = []
    for Bn in L["stream_B"]:
        for W in L["mxu_words"]:
            mxu_mb_templates.append(
                ((mxu_S, mxu_T),) * Bn
                + ((stream_B_ax, mxu_chunk_ax, mxu_K),
                   (stream_B_ax, mxu_chunk_ax, mxu_K),
                   (stream_B_ax, mxu_chunk_ax),
                   (stream_B_ax, mxu_chunk_ax), (stream_B_ax,))
                + (((mxu_F,),) * W
                   + ((mxu_F,), (one,), (one,), (one,))) * Bn)
    # the kernel rung's chunk call: one spec chunk + offsets + the
    # (ws, stat, res) carry + packed table — same axes as the
    # pallas-stream-scan ladder, single-chunk form
    stream_kernel_templates = []
    for W in L["kernel_words"]:
        stream_kernel_templates.append(
            ((stream_chunk_ax, width), (off2,))
            + ((rows, lane),) * W
            + ((one, lane), (res8, lane), (table_rows, lane)))
    # kernel-rung megabatch: lane-major packed chunks (B, chunk,
    # 2+2K), per-lane (offset, nt) rows, B-tuples of (ws, stat, res,
    # table) — one Mosaic build shared across lanes inside one jit
    stream_kernel_mb_templates = []
    for Bn in L["stream_B"]:
        for W in L["kernel_words"]:
            stream_kernel_mb_templates.append(
                ((stream_B_ax, stream_chunk_ax, width),
                 (stream_B_ax, off2))
                + (((rows, lane),) * W
                   + ((one, lane), (res8, lane),
                      (table_rows, lane))) * Bn)

    # workload-family sites (checker/wl, docs/workloads.md): batched
    # column-plane reductions — no frontier, every jit-visible dim an
    # enum rung of the WL_* ladders. The delta forms are the stream
    # rungs (stream/wl.py): solo advance + the megabatched advance
    # (per-lane carries pass as tuples and stack INSIDE the jit, delta
    # planes arrive lane-major on the MEGABATCH_LANES ladder).
    wl_B = Axis("wl_B", "enum", values=L["wl_batch"])
    wl_R = Axis("wl_reads", "enum", values=L["wl_reads"])
    wl_A = Axis("wl_accounts", "enum", values=L["wl_accounts"])
    wl_T = Axis("wl_snaps", "enum", values=L["wl_snaps"])
    wl_E = Axis("wl_elems", "enum", values=L["wl_elems"])
    wl_N = Axis("wl_nodes", "enum", values=L["wl_nodes"])
    wl_V = Axis("wl_values", "enum", values=L["wl_values"])
    wl_D = Axis("wl_delta", "enum", values=L["wl_delta"])
    # wl_bank_check(reads, read_mask, wrong_n, init, transfers, total)
    wl_bank_tmpl = ((wl_B, wl_R, wl_A), (wl_B, wl_R), (wl_B, wl_R),
                    (wl_B, wl_A), (wl_B, wl_T, wl_A), (wl_B,))
    # wl_bank_delta(balance, reads, read_mask, wrong_n, transfers,
    # total-scalar) — delta rows on the WL_DELTA_PADS ladder
    wl_bank_delta_tmpl = ((wl_A,), (wl_D, wl_A), (wl_D,), (wl_D,),
                          (wl_D, wl_A), ())
    wl_bank_mb_tmpls = []
    for Bn in L["stream_B"]:
        wl_bank_mb_tmpls.append(
            ((wl_A,),) * Bn
            + ((stream_B_ax, wl_D, wl_A), (stream_B_ax, wl_D),
               (stream_B_ax, wl_D), (stream_B_ax, wl_D, wl_A),
               (stream_B_ax,)))
    # wl_sets_check(attempts, adds, final_read, has_read)
    wl_sets_tmpl = ((wl_B, wl_E),) * 3 + ((wl_B,),)
    # wl_sets_delta(3 carry planes, 3 delta planes, 2 scalars)
    wl_sets_delta_tmpl = ((wl_E,),) * 6 + ((), ())
    wl_sets_mb_tmpls = []
    for Bn in L["stream_B"]:
        wl_sets_mb_tmpls.append(
            ((wl_E,),) * (3 * Bn)
            + ((stream_B_ax, wl_E),) * 3
            + ((stream_B_ax,), (stream_B_ax,)))
    # wl_dirty_check(failed, reads, node_mask, read_mask)
    wl_dirty_tmpl = ((wl_B, wl_V), (wl_B, wl_R, wl_N),
                     (wl_B, wl_R, wl_N), (wl_B, wl_R))

    sites = (
        Site(
            key="pallas-stream-scan",
            jit_names=("seg_scan", "seg_stream_scan", "seg_chunk",
                       "run_sharded", "carry_reset"),
            note="fused-kernel chunk scan (checker/pallas_seg._scan_fn)"
                 ": one Mosaic program per (SegKernelSpec, b_pad, "
                 "stream); specs are drawn from the production tier "
                 "table (pallas_budget.production_tiers), b_pad is the "
                 "pow2 results-buffer bucket, chunk count is the "
                 "chunked-engine scan length (linear by design). "
                 "`seg_scan` is the single-history form and "
                 "`seg_stream_scan` the multi-history stream form. "
                 "`seg_chunk` (pallas_seg._chunk_jit) is one chunk "
                 "call, dispatched chunk by chunk by the progress "
                 "path and the counterexample re-scan. "
                 "`run_sharded` (pallas_seg._sharded_scan_fn) is the "
                 "shard_map form: the SAME per-shard kernel body with "
                 "a leading mesh axis D on every per-shard tensor — "
                 "per-shard shapes are the global shapes divided by D. "
                 "`carry_reset` (pallas_seg._reset_fn) is the "
                 "donated-carry recycle program: constants into a "
                 "donated (ws, stat) carry set, one per (spec, b_pad) "
                 "the run ladder already admits",
            templates=tuple(run_templates)
            + tuple(chunk_templates)
            + tuple(run_sharded_templates)
            + tuple(reset_templates),
            axes_doc=(chunk, width, rows, table_rows, b_pad, mesh_D,
                      Axis("n_words", "enum",
                           values=L["kernel_words"]), n_chunks),
        ),
        Site(
            key="xla-batch-engines",
            jit_names=("check_device_keys", "check_device_seg_batch",
                       "check_device_keys_sharded"),
            note="batched XLA engines (checker/linear_jax): segment "
                 "tensors (S, B, K) with every axis pow2 "
                 "(segment_batch pads, service buckets floor), memo "
                 "table dims pow2 (pad_succ). `check_device_seg_batch` "
                 "is the last resort, the segmented search vmapped "
                 "over lanes. "
                 "`check_device_keys_sharded` shard_maps the keys "
                 "body over the mesh batch axis: global shapes are "
                 "identical (B pow2, padded to a multiple of D), each "
                 "shard compiles B/D lanes",
            templates=(xla_batch_seg,),
            axes_doc=(memo, S, B, K),
        ),
        Site(
            key="mxu-frontier",
            jit_names=("check_device_mxu_batch", "check_device_mxu",
                       "check_device_mxu_chunk"),
            note="MXU frontier engine (checker/mxu): BFS-as-matmul "
                 "closure for wide-P histories — packed-word frontier, "
                 "bf16/f32 one-hot expansion on the MXU, exact "
                 "packed-key lexsort dedup. Batch form takes the same "
                 "(S, B, K) segment tensors as keys; table dims "
                 "are pow2 inside the matmul caps (S_CAP x T_CAP). "
                 "The chunk form's carry exposes the frontier as "
                 "n_words (F,) word columns with F drawn from the "
                 "CAPACITIES ladder (in-place escalation rungs); P is "
                 "a static arg bucketed by the caller (driver "
                 "even-buckets, batch pow2-buckets, P <= MAX_P)",
            templates=(mxu_batch_tmpl, mxu_single_tmpl)
            + tuple(mxu_chunk_tmpls),
            axes_doc=(mxu_S, mxu_T, S, B, mxu_K, mxu_F, mxu_chunk_ax,
                      mxu_words_ax),
        ),
        Site(
            key="stream-delta",
            jit_names=("stream_delta_chunk", "stream_kernel_delta",
                       "stream_delta_megabatch",
                       "stream_kernel_delta_mb",
                       "check_device_mxu_megabatch"),
            note="streaming-session delta dispatch (stream/engine): "
                 "the ONE device entry an append reaches. "
                 "`stream_delta_chunk` is the XLA rung — delta "
                 "segment tensors on the DELTA_PADS pow2 ladder, K "
                 "pow2 up to the MXU P ceiling, the resident carry "
                 "(states/slots/valid + scalars) at a "
                 "STREAM_CAPACITIES frontier rung and an even-"
                 "bucketed slot width; memo dims pow2 (pad_sizes). "
                 "`stream_kernel_delta` is the kernel rung's chunk "
                 "call (same Mosaic program family as "
                 "pallas-stream-scan, re-jitted under a declared "
                 "serving name; delta_spec adds the STREAM_CHUNKS "
                 "small-delta rungs). The MXU rung rides the "
                 "mxu-frontier site's chunk form with delta pads "
                 "floored to its chunk ladder (MXU_DELTA_FLOOR). "
                 "The `*_megabatch`/`*_mb` forms are the round-13 "
                 "fused advance: a beat's same-shape-class lanes "
                 "stack onto the session_B pow2 ladder (pad = "
                 "duplicate lane 0) and run as ONE program per "
                 "rung — B-tuples of per-lane memo tables and "
                 "carries, lane-major delta tensors",
            templates=tuple(stream_templates)
            + tuple(stream_mb_templates)
            + tuple(mxu_mb_templates)
            + tuple(stream_kernel_templates)
            + tuple(stream_kernel_mb_templates),
            axes_doc=(stream_delta_ax, stream_K, stream_F_ax,
                      stream_P_ax, stream_B_ax, stream_chunk_ax,
                      memo),
        ),
        Site(
            key="wl-bank",
            jit_names=("wl_bank_check", "wl_bank_delta",
                       "wl_bank_delta_mb"),
            note="bank workload family (checker/wl/bank.py, "
                 "docs/workloads.md): balance tensors -> wrong-total/"
                 "wrong-n/snapshot-inconsistency in ONE program. "
                 "`wl_bank_check` is the post-hoc batch form — lanes "
                 "on the WL_BATCH ladder, reads/snapshots/accounts on "
                 "their WL_* rungs (stage_wl_batch buckets; over-rung "
                 "histories degrade to the host oracle). "
                 "`wl_bank_delta` is the stream rung's solo advance "
                 "(carry = the (A,) running balance; delta rows on "
                 "WL_DELTA_PADS); `wl_bank_delta_mb` is its fused "
                 "megabatch form — per-lane carries as tuples stacked "
                 "inside the jit, lane-major deltas on the "
                 "MEGABATCH_LANES ladder, vmapping the SAME body "
                 "(bit-identical per lane)",
            templates=(wl_bank_tmpl, wl_bank_delta_tmpl)
            + tuple(wl_bank_mb_tmpls),
            axes_doc=(wl_B, wl_R, wl_A, wl_T, wl_D, stream_B_ax),
        ),
        Site(
            key="wl-sets",
            jit_names=("wl_sets_check", "wl_sets_delta",
                       "wl_sets_delta_mb"),
            note="sets workload family (checker/wl/sets.py): "
                 "per-element bool membership planes — lost/phantom "
                 "as bitmap algebra. `wl_sets_check` is the post-hoc "
                 "batch form (element universe on the WL_ELEMS "
                 "ladder); `wl_sets_delta`/`wl_sets_delta_mb` are the "
                 "stream rungs (carry = three (E,) planes; in-place "
                 "element-rung escalation re-uploads on the next "
                 "dispatch, past the top rung the session answers "
                 "terminal UNKNOWN)",
            templates=(wl_sets_tmpl, wl_sets_delta_tmpl)
            + tuple(wl_sets_mb_tmpls),
            axes_doc=(wl_B, wl_E, stream_B_ax),
        ),
        Site(
            key="wl-dirty",
            jit_names=("wl_dirty_check",),
            note="dirty-reads workload family (checker/wl/dirty.py): "
                 "failed-write table joined against read-visibility "
                 "planes + per-node disagreement in one program. "
                 "Post-hoc ONLY (the verdict joins reads against the "
                 "FULL failed-write set — no O(delta) carry exists), "
                 "so there is no stream rung; value universe on the "
                 "WL_VALUES ladder, node views on WL_NODES",
            templates=(wl_dirty_tmpl,),
            axes_doc=(wl_B, wl_R, wl_N, wl_V),
        ),
        Site(
            key="xla-driver-engines",
            jit_names=("check_device", "check_device_seg",
                       "check_device_seg_chunk", "check_device_seg2",
                       "check_device_seg2_chunk", "pending_histogram",
                       "check_device_batch"),
            note="single-history adaptive driver (checker/linear.py) "
                 "and the event-level test oracle "
                 "(`check_device_batch`, dense step streams): compile "
                 "per history shape BY DESIGN — an OPEN site, outside "
                 "the closure guarantee. The per-item-dispatch lint "
                 "rule keeps serving traffic off this path; the "
                 "closed serving surface is the batch/stream/txn "
                 "sites above",
            open_site=True,
            bound_note="open (one program per history shape; "
                       "single-history driver path only)",
        ),
        Site(
            key="txn-closure",
            jit_names=("closure_diag_kernel",
                       "closure_diag_kernel_sharded"),
            note="txn matrix-closure engine (txn/closure_jax): packed "
                 "adjacency planes (4, N, N/8) or (B, 4, N, N/8); N "
                 "pow2 >= TXN_N_FLOOR (service cap 4096, offline "
                 "shrink may go wider), B pow2 (service pads). The "
                 "sharded form (shard_map over the batch axis, B a "
                 "pow2 multiple of D) sees the same global shapes; "
                 "each shard squares B/D adjacency stacks",
            templates=(((four, N, N8),), ((txn_B, four, N, N8),)),
            axes_doc=(N, txn_B, mesh_D),
        ),
    )
    return Inventory(sites=sites, infra_names=INFRA_NAMES)


# --- eval_shape witnesses ---------------------------------------------------

def _witness_specs():
    """(site_key, describe, thunk) triples; each thunk builds the
    ShapeDtypeStruct args and runs ``jax.eval_shape`` on the REAL
    entry point (abstract trace only — no compile, no device)."""
    import functools

    import jax
    import numpy as np

    i32 = np.int32

    def st(shape, dt=i32):
        return jax.ShapeDtypeStruct(shape, dt)

    def kernel_witness():
        from ..checker import pallas_seg as PS

        spec = PS.spec_for(8, 32, 4, 2)
        assert spec is not None
        run = PS._scan_fn(spec, 8, True)
        W = spec.n_words
        return jax.eval_shape(
            run, st((2, spec.chunk, 2 + 2 * spec.K)),
            tuple(st((PS.carry_rows(spec), 128)) for _ in range(W)),
            st((1, 128)), st((8, 128)),
            st((spec.table_rows_pad, 128)), 32)

    def chunk_witness():
        from ..checker import pallas_seg as PS

        spec = PS.spec_for(8, 32, 4, 2)
        assert spec is not None
        W = spec.n_words
        return jax.eval_shape(
            PS._chunk_jit(spec), st((spec.chunk, 2 + 2 * spec.K)),
            st((2,)),
            tuple(st((PS.carry_rows(spec), 128)) for _ in range(W)),
            st((1, 128)), st((8, 128)),
            st((spec.table_rows_pad, 128)))

    def carry_reset_witness():
        from ..checker import pallas_seg as PS

        spec = PS.spec_for(8, 32, 4, 2)
        assert spec is not None
        reset = PS._reset_fn(spec, 8)
        W = spec.n_words
        return jax.eval_shape(
            reset,
            tuple(st((PS.carry_rows(spec), 128)) for _ in range(W)),
            st((1, 128)))

    def keys_witness():
        from ..checker import linear_jax as LJ

        fn = functools.partial(LJ.check_device_keys, B=4, F=64, P=2,
                               n_states=16, n_transitions=16)
        return jax.eval_shape(fn, st((16, 16)), st((8, 4, 2)),
                              st((8, 4, 2)), st((8, 4)), st((8,)))

    def seg_batch_witness():
        from ..checker import linear_jax as LJ

        fn = functools.partial(LJ.check_device_seg_batch, F=64, P=2,
                               n_states=16, n_transitions=16)
        return jax.eval_shape(fn, st((16, 16)), st((8, 4, 2)),
                              st((8, 4, 2)), st((8, 4)), st((8,)))

    def closure_witness():
        from ..txn import closure_jax as CJ

        return jax.eval_shape(CJ._jitted(16),
                              st((4, 16, 2), np.uint8))

    def mxu_witness():
        from ..checker import mxu as MXU

        fn = functools.partial(MXU.check_device_mxu_batch, B=2,
                               F=1024, P=16, n_states=32,
                               n_transitions=32)
        return jax.eval_shape(fn, st((32, 32)), st((8, 2, 2)),
                              st((8, 2, 2)), st((8, 2)), st((8,)))

    def stream_delta_witness():
        from ..stream import engine as SE

        fn = functools.partial(SE.stream_delta_chunk, F=256, Fs=32,
                               P=2, n_states=16, n_transitions=16)
        carry = (st((256,)), st((256, 2)), st((256,), np.bool_),
                 st(()), st(()), st(()))
        return jax.eval_shape(fn, st((16, 16)), st((16, 2)),
                              st((16, 2)), st((16,)), st((16,)),
                              st(()), carry)

    def stream_megabatch_witness():
        from ..stream import engine as SE

        fn = functools.partial(SE.stream_delta_megabatch, F=256,
                               Fs=32, P=2, n_states=16,
                               n_transitions=16)
        carry = (st((256,)), st((256, 2)), st((256,), np.bool_),
                 st(()), st(()), st(()))
        return jax.eval_shape(fn, (st((16, 16)),) * 2,
                              st((2, 16, 2)), st((2, 16, 2)),
                              st((2, 16)), st((2, 16)), st((2,)),
                              (carry, carry))

    def mxu_megabatch_witness():
        from ..checker import mxu as MXU

        lane = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            MXU.init_carry(1, 1024, 16, 32, 32))
        fn = functools.partial(MXU._megabatch_jit, F=1024, P=16,
                               n_states=32, n_transitions=32)
        return jax.eval_shape(fn, (st((32, 32)),) * 2,
                              st((2, 64, 2)), st((2, 64, 2)),
                              st((2, 64)), st((2, 64)), st((2,)),
                              (lane, lane))

    def stream_kernel_mb_witness():
        from ..checker import pallas_seg as PS
        from ..stream import engine as SE

        spec = PS.spec_for(8, 32, 4, 2)
        assert spec is not None
        dspec = PS.delta_spec(spec, 16)
        fn = SE.stream_kernel_megabatch(dspec, 2)
        W = spec.n_words
        lane = (tuple(st((PS.carry_rows(spec), 128))
                      for _ in range(W)),
                st((1, 128)), st((8, 128)),
                st((spec.table_rows_pad, 128)))
        return jax.eval_shape(
            fn, st((2, dspec.chunk, 2 + 2 * spec.K)), st((2, 2)),
            (lane, lane))

    def wl_bank_witness():
        from ..checker.wl import bank as WB

        fn = functools.partial(WB.wl_bank_check, n_reads=8,
                               n_accounts=8, n_snaps=8)
        return jax.eval_shape(fn, st((8, 8, 8)),
                              st((8, 8), np.bool_),
                              st((8, 8), np.bool_), st((8, 8)),
                              st((8, 8, 8)), st((8,)))

    def wl_bank_mb_witness():
        from ..checker.wl import bank as WB

        fn = functools.partial(WB.wl_bank_delta_mb, n_reads=8,
                               n_accounts=8, n_snaps=8)
        return jax.eval_shape(fn, (st((8,)),) * 2, st((2, 8, 8)),
                              st((2, 8), np.bool_),
                              st((2, 8), np.bool_), st((2, 8, 8)),
                              st((2,)))

    def wl_sets_witness():
        from ..checker.wl import sets as WS

        fn = functools.partial(WS.wl_sets_check, n_elems=128)
        return jax.eval_shape(fn, st((8, 128), np.bool_),
                              st((8, 128), np.bool_),
                              st((8, 128), np.bool_),
                              st((8,), np.bool_))

    def wl_sets_mb_witness():
        from ..checker.wl import sets as WS

        fn = functools.partial(WS.wl_sets_delta_mb, n_elems=128)
        lane = (st((128,), np.bool_),) * 3
        return jax.eval_shape(fn, (lane, lane),
                              st((2, 128), np.bool_),
                              st((2, 128), np.bool_),
                              st((2, 128), np.bool_),
                              st((2,), np.bool_), st((2,), np.bool_))

    def wl_dirty_witness():
        from ..checker.wl import dirty as WD

        fn = functools.partial(WD.wl_dirty_check, n_reads=8,
                               n_nodes=4, n_values=128)
        return jax.eval_shape(fn, st((8, 128), np.bool_),
                              st((8, 8, 4)),
                              st((8, 8, 4), np.bool_),
                              st((8, 8), np.bool_))

    def _witness_mesh():
        # a 1-device mesh: available on every platform, and the D=1
        # rung keeps the artifact deterministic across environments
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.devices()[:1]), ("batch",))

    def kernel_sharded_witness():
        from ..checker import pallas_seg as PS

        spec = PS.spec_for(8, 32, 4, 2)
        assert spec is not None
        run = PS._sharded_scan_fn(spec, 8, _witness_mesh(), "batch")
        W = spec.n_words
        return jax.eval_shape(
            run, st((1, 2, spec.chunk, 2 + 2 * spec.K)),
            tuple(st((1, PS.carry_rows(spec), 128))
                  for _ in range(W)),
            st((1, 1, 128)), st((1, 8, 128)),
            st((spec.table_rows_pad, 128)), 32)

    def keys_sharded_witness():
        from ..checker import linear_jax as LJ

        fn = LJ._sharded_keys_fn(_witness_mesh(), "batch", 4, 64, 2,
                                 16, 16)
        return jax.eval_shape(fn, st((16, 16)), st((8, 4, 2)),
                              st((8, 4, 2)), st((8, 4)), st((8,)))

    def closure_sharded_witness():
        from ..txn import closure_jax as CJ

        return jax.eval_shape(
            CJ._jitted_sharded(16, _witness_mesh()),
            st((2, 4, 16, 2), np.uint8))

    return (
        ("pallas-stream-scan",
         "spec_for(8,32,P=4,K=2), 2 chunks, b_pad=8", kernel_witness),
        ("pallas-stream-scan",
         "seg_chunk: same spec, one chunk", chunk_witness),
        ("pallas-stream-scan",
         "run_sharded: same spec, D=1 mesh rung",
         kernel_sharded_witness),
        ("pallas-stream-scan",
         "carry_reset: same spec carry shapes, b_pad=8",
         carry_reset_witness),
        ("xla-batch-engines",
         "check_device_keys at (ns,nt)=(16,16) S=8 B=4 K=2",
         keys_witness),
        ("xla-batch-engines",
         "check_device_seg_batch at (ns,nt)=(16,16) S=8 B=4 K=2",
         seg_batch_witness),
        ("xla-batch-engines",
         "check_device_keys_sharded: same shapes, D=1 mesh rung",
         keys_sharded_witness),
        ("mxu-frontier",
         "check_device_mxu_batch at (32,32) S=8 B=2 K=2 P=16 F=1024",
         mxu_witness),
        ("stream-delta",
         "stream_delta_chunk at (16,16) delta=16 K=2 F=256 P=2",
         stream_delta_witness),
        ("stream-delta",
         "stream_delta_megabatch: same rung fused at session_B=2",
         stream_megabatch_witness),
        ("stream-delta",
         "check_device_mxu_megabatch at (32,32) delta=64 P=16 "
         "F=1024, session_B=2",
         mxu_megabatch_witness),
        ("stream-delta",
         "stream_kernel_delta_mb: spec_for(8,32,P=4,K=2) at "
         "delta_spec chunk=64, session_B=2",
         stream_kernel_mb_witness),
        ("wl-bank",
         "wl_bank_check at B=8 R=8 A=8 T=8", wl_bank_witness),
        ("wl-bank",
         "wl_bank_delta_mb: delta=8 A=8 fused at session_B=2",
         wl_bank_mb_witness),
        ("wl-sets",
         "wl_sets_check at B=8 E=128", wl_sets_witness),
        ("wl-sets",
         "wl_sets_delta_mb: E=128 fused at session_B=2",
         wl_sets_mb_witness),
        ("wl-dirty",
         "wl_dirty_check at B=8 R=8 N=4 V=128", wl_dirty_witness),
        ("txn-closure", "closure bucket N=16", closure_witness),
        ("txn-closure",
         "closure_diag_kernel_sharded: B=2 N=16, D=1 mesh rung",
         closure_sharded_witness),
    )


def trace_witnesses() -> List[Finding]:
    """Abstractly trace one witness rung per site; a ladder whose
    shapes no longer trace is a ``compile-surface-trace`` finding."""
    from .jaxpr_audit import _force_cpu

    if not _force_cpu():
        return [Finding(
            "compile-surface-trace", __file__, 0,
            "a non-CPU jax backend was initialized before the prover "
            "could pin the platform — run with JAX_PLATFORMS=cpu")]
    out: List[Finding] = []
    for key, desc, thunk in _witness_specs():
        try:
            thunk()
        except Exception as e:          # a broken ladder IS a finding
            out.append(Finding(
                "compile-surface-trace", __file__, 0,
                f"site {key}: witness '{desc}' failed to trace: "
                f"{type(e).__name__}: {e}"))
    return out


def witness_table() -> List[Tuple[str, str, str]]:
    """(site, witness, out-shapes) rows for the artifact. Raises
    (rather than silently emitting an empty table) when the platform
    can't be pinned — a PROGRAMS.md missing its witness rows would
    fail the golden test as unexplained drift."""
    import jax

    from .jaxpr_audit import _force_cpu

    if not _force_cpu():
        raise RuntimeError(
            "cannot regenerate the witness table: a non-CPU jax "
            "backend was initialized before the prover could pin the "
            "platform — rerun with JAX_PLATFORMS=cpu in a fresh "
            "process")
    rows = []
    for key, desc, thunk in _witness_specs():
        try:
            out = thunk()
            shapes = jax.tree.map(lambda x: tuple(x.shape), out)
            rows.append((key, desc, str(shapes)))
        except Exception as e:
            rows.append((key, desc, f"TRACE FAILED: {type(e).__name__}"))
    return rows


# --- the PROGRAMS.md artifact -----------------------------------------------

def render_programs() -> str:
    """The compile-surface inventory as a deterministic markdown
    artifact (the drift contract of ``PROGRAMS.md``: tier-1
    regenerates this and any diff is a failure)."""
    L = _ladders()
    inv = static_inventory()
    lim = L["limits"]
    out = [
        "# Compile-surface inventory",
        "",
        "Generated by `python -m comdb2_tpu.analysis --programs "
        "PROGRAMS.md`; checked by `tests/test_compile_surface.py`",
        "(drift = failure, same contract as the budget table). Every",
        "program XLA or Mosaic may compile for the serving surface is",
        "drawn from the ladders below; the runtime guard",
        "(`comdb2_tpu.utils.compile_guard`) asserts observed compiles",
        "stay a subset.",
        "",
        "## Declared ladders",
        "",
        "| ladder | values | source |",
        "|---|---|---|",
        f"| fuzz kernel buckets | {list(L['fuzz_buckets'])} | "
        "`analysis.pallas_budget.PRODUCTION_BUCKETS` |",
        f"| service n_pad | pow2 {L['service_n_pad'][0]}.."
        f"{L['service_n_pad'][1]} | `ServiceLimits.max_ops="
        f"{lim.max_ops}` |",
        f"| service S | pow2 {L['service_S'][0]}..{L['service_S'][1]}"
        f" | `ServiceLimits.max_segments={lim.max_segments}` |",
        f"| service K | pow2 {L['service_K'][0]}..{L['service_K'][1]}"
        f" | `ServiceLimits.max_invokes_per_seg="
        f"{lim.max_invokes_per_seg}` |",
        f"| service P | pow2 {L['service_P'][0]}..{L['service_P'][1]}"
        f" | `ServiceLimits.max_processes={lim.max_processes}` |",
        f"| service P_eff | even 2..{lim.max_slots} | "
        f"`ServiceLimits.max_slots={lim.max_slots}` |",
        f"| txn closure N | pow2 {L['txn_N'][0]}..{L['txn_N'][1]} | "
        f"`txn.edges.TXN_N_FLOOR`, `ServiceLimits.max_txns="
        f"{lim.max_txns}` (service cap; offline shrink may go wider) |",
        f"| mesh shard axis D | pow2 {L['mesh_D'][0]}.."
        f"{L['mesh_D'][1]} | `service.sharding.MAX_SHARDS`; per-shard "
        "shapes are the bucketed global shapes divided by D (both "
        "pow2, so the division stays on the ladder) |",
        f"| shrink kept-op buckets | pow2 {L['shrink_bucket'][0]}.."
        f"{L['shrink_bucket'][1]} | `shrink.verdicts.MIN_BUCKET` |",
        f"| shrink batch B | pow2 {L['shrink_B'][0]}.."
        f"{L['shrink_B'][1]} | `shrink.verdicts.MAX_BATCH` |",
        f"| memo table dims | pow2 {L['memo_dim'][0]}.."
        f"{L['memo_dim'][1]} | `pad_succ(next_pow2(...))` at every "
        "dispatch path |",
        f"| kernel chunk | {list(L['kernel_chunks'])} (+16 interpret)"
        " | `spec_for` SMEM bound per K |",
        f"| kernel widths (2+2K) | {list(L['kernel_widths'])} | "
        "K = 1..8 |",
        f"| kernel carry rows | {list(L['kernel_rows'])} | "
        "(8,128)/(16,128) tiers + `pallas_seg.WIDE_ROWS` wide-rung "
        "rows |",
        f"| kernel frontier rungs | {list(L['kernel_rungs'])} | "
        "per segment inside `seg_frontier`; runtime sizes, no "
        "program axis |",
        f"| kernel table rows | {list(L['kernel_table_rows'])} | "
        "`table_rows_pad` buckets |",
        f"| mxu table caps | pow2 1..{L['mxu_table'][0]} x pow2 1.."
        f"{L['mxu_table'][1]} | `checker.mxu.S_CAP/T_CAP` (bf16 "
        "value-plane exactness bound) |",
        f"| mxu frontier F | {list(L['mxu_F'])} | "
        "`checker.mxu.CAPACITIES` (in-place escalation rungs; top "
        "rung = the wide-P honest-UNKNOWN threshold) |",
        f"| mxu chunk | pow2 {L['mxu_chunk'][0]}..{L['mxu_chunk'][1]}"
        " | `checker.mxu.CHUNK` |",
        f"| mxu P crossover | {L['mxu_P'][0]}..{L['mxu_P'][1]} | "
        "`checker.mxu.MIN_P/MAX_P` (static arg — driver even-buckets, "
        "batch pow2-buckets) |",
        f"| mxu key words | {list(L['mxu_words'])} | "
        "`PackPlan.n_words` over the table caps x P |",
        f"| stream delta_pad | {list(L['stream_delta'])} | "
        "`stream.engine.DELTA_PADS` (session appends bucket onto it; "
        "larger deltas split; MXU rung floors at MXU_DELTA_FLOOR) |",
        f"| stream frontier F | {list(L['stream_F'])} | "
        "`stream.engine.STREAM_CAPACITIES` (in-place "
        "expand_seg_carry escalation rungs) |",
        f"| stream P | even {L['stream_P'][0]}..{L['stream_P'][-1]} |"
        " session slot width (renamed concurrency, even-bucketed; "
        "in-place expand_seg_carry_slots widening) |",
        f"| stream session B | {list(L['stream_B'])} | "
        "`stream.engine.MEGABATCH_LANES` (fused-advance lane rungs; "
        "short groups pad by duplicating lane 0, single lanes go "
        "solo) |",
        f"| stream kernel small chunks | "
        f"{list(L['stream_small_chunks'])} | "
        "`pallas_seg.STREAM_CHUNKS` (`delta_spec` small-delta rungs "
        "under the stream jit names; base chunks stay spec_for's) |",
        f"| wl batch B | {list(L['wl_batch'])} | "
        "`checker.wl.batch.WL_BATCH` (histories per dispatch; bigger "
        "batches chunk, short ones pad by duplicating lane 0) |",
        f"| wl reads | {list(L['wl_reads'])} | "
        "`checker.wl.batch.WL_READS` (bank + dirty ok-read rows per "
        "history; over-rung degrades to the host oracle) |",
        f"| wl accounts | {list(L['wl_accounts'])} | "
        "`checker.wl.batch.WL_ACCOUNTS` (bank balance-row width) |",
        f"| wl snapshots | {list(L['wl_snaps'])} | "
        "`checker.wl.batch.WL_SNAPS` (bank transfer rows; snapshot "
        "plane depth is T + 1) |",
        f"| wl elements | {list(L['wl_elems'])} | "
        "`checker.wl.batch.WL_ELEMS` (sets element universe; stream "
        "sessions escalate IN PLACE up this ladder) |",
        f"| wl nodes | {list(L['wl_nodes'])} | "
        "`checker.wl.batch.WL_NODES` (dirty per-read node views) |",
        f"| wl values | {list(L['wl_values'])} | "
        "`checker.wl.batch.WL_VALUES` (dirty distinct-value "
        "universe) |",
        f"| wl delta rows | {list(L['wl_delta'])} | "
        "`checker.wl.batch.WL_DELTA_PADS` (stream-rung per-append "
        "read/transfer row pads; oversized appends chunk solo) |",
        "",
        "## Dispatch sites",
        "",
    ]
    for site in inv.sites:
        out.append(f"### {site.key}")
        out.append("")
        out.append(f"- jit names: {', '.join(site.jit_names)}")
        out.append(f"- {site.note}")
        if site.axes_doc:
            out.append("- axes: " + "; ".join(
                f"{ax.name} in {ax.describe()}"
                for ax in site.axes_doc))
        out.append(f"- program bound: {site.program_bound()}")
        out.append("")
    nspecs = len(L["specs"])
    out += [
        "## Kernel spec tiers",
        "",
        f"{nspecs} distinct `SegKernelSpec` tiers are reachable from "
        "the production bucket ladder x P(1..15) x K(1..8) — the full "
        "per-tier budget table is the `--budget-table` artifact.",
        "",
        "## Abstract-trace witnesses (jax.eval_shape)",
        "",
        "| site | witness | out shapes |",
        "|---|---|---|",
    ]
    for key, desc, shapes in witness_table():
        out.append(f"| {key} | {desc} | {shapes} |")
    out += [
        "",
        "## Infra allowlist",
        "",
        "Host-glue programs (scalar converts, index builders) are",
        "name-allowlisted, not shape-constrained:",
        "",
        "`" + "`, `".join(sorted(INFRA_NAMES)) + "`",
        "",
    ]
    return "\n".join(out) + ""


# --- the unbucketed-dispatch-site AST rule ----------------------------------

#: sinks: callee name -> shape-carrying argument spec. Deliberately the
#: BATCH/SERVING surface only — the single-history driver's adaptive
#: path passes exact sizes on purpose (spec_for/pad_succ bucket them
#: downstream) and is declared an OPEN site in the runtime inventory.
SHAPE_SINKS: Dict[str, dict] = {
    "check_batch": {"kwargs": ("s_pad", "k_pad", "n_states_pad",
                               "n_transitions_pad", "p_eff_pad")},
    "check_batch_async": {"kwargs": ("s_pad", "k_pad",
                                     "n_states_pad",
                                     "n_transitions_pad",
                                     "p_eff_pad")},
    "segment_batch": {"kwargs": ("s_pad", "k_pad")},
    "make_segments": {"kwargs": ("s_pad", "k_pad")},
    "pad_succ": {"kwargs": ("s_pad", "t_pad"), "pos": (1, 2)},
    "check_device_keys": {"kwargs": ("n_states", "n_transitions")},
    "check_device_seg_batch": {"kwargs": ("n_states",
                                          "n_transitions")},
    "check_device_mxu_batch": {"kwargs": ("n_states",
                                          "n_transitions")},
    "check_device_batch": {"kwargs": ("n_states", "n_transitions")},
    "check_device_pallas_stream": {"kwargs": ("n_states",
                                              "n_transitions")},
    # mesh sinks: a shard_map body fed a shape not divided from a
    # declared bucket compiles one per-shard program per seed — B must
    # be a pow2 multiple of D, table dims pow2, like everywhere else
    "check_device_keys_sharded": {"kwargs": ("B", "n_states",
                                             "n_transitions")},
    "stream_dispatch_sharded": {"kwargs": ("n_states",
                                           "n_transitions")},
    "check_sharded": {"kwargs": ("n_states", "n_transitions")},
    # the streaming-session delta entrypoints: raw memo counts here
    # would compile one program per live history's alphabet — every
    # caller must route through stream.engine.pad_sizes. The fused
    # megabatch forms are the same sink (one unbucketed lane would
    # seed a program for the WHOLE group's shape class)
    "stream_delta_chunk": {"kwargs": ("n_states", "n_transitions")},
    "stream_delta_megabatch": {"kwargs": ("n_states",
                                          "n_transitions")},
    "check_device_mxu_megabatch": {"kwargs": ("n_states",
                                              "n_transitions")},
    # workload-family sinks (checker/wl): every static dim must come
    # off a WL_* ladder (stage_wl_batch/_dims bucket; stream/wl.py
    # sessions carry pre-bucketed pads) — a raw count here compiles
    # one program per distinct history shape, same hazard as the
    # frontier engines
    "wl_bank_check": {"kwargs": ("n_reads", "n_accounts",
                                 "n_snaps")},
    "wl_bank_delta": {"kwargs": ("n_reads", "n_accounts",
                                 "n_snaps")},
    "wl_bank_delta_mb": {"kwargs": ("n_reads", "n_accounts",
                                    "n_snaps")},
    "wl_sets_check": {"kwargs": ("n_elems",)},
    "wl_sets_delta": {"kwargs": ("n_elems",)},
    "wl_sets_delta_mb": {"kwargs": ("n_elems",)},
    "wl_dirty_check": {"kwargs": ("n_reads", "n_nodes",
                                  "n_values")},
    "check_wl_batch": {"kwargs": ("b_pad",)},
    "stage_wl_batch": {"kwargs": ("b_pad",)},
}

#: callables that PRODUCE bucketed values
SANCTIONERS = {"next_pow2", "_next_pow2", "bucket_of", "padded"}

#: attribute reads that are raw per-history counts (memo tables)
RAW_ATTRS = {"n_states", "n_transitions"}

#: attribute reads that are ladder-derived by construction
#: (service Bucket / TxnBucket fields)
BUCKETED_ATTRS = {"S", "K", "P", "P_eff", "n_pad", "N"}

_MAX_DEPTH = 5

BUCKETED, RAW, UNKNOWN = 0, 1, 2


@dataclass
class _FileInfo:
    path: str
    tree: ast.Module
    lines: List[str]
    funcs: Dict[str, ast.AST] = field(default_factory=dict)
    consts: Dict[str, int] = field(default_factory=dict)


class _Graph:
    """Cross-file call graph over the scanned set: function defs by
    name (chased only when unambiguous or same-file) and call sites by
    callee name."""

    def __init__(self, infos: List[_FileInfo]):
        self.infos = infos
        self.defs: Dict[str, List[Tuple[_FileInfo, ast.AST]]] = {}
        # callee name -> [(info, call node, enclosing funcdef | None)]
        self.calls: Dict[str, List[tuple]] = {}
        for info in infos:
            self._index(info)

    @staticmethod
    def _callee(call: ast.Call) -> str:
        fn = call.func
        if isinstance(fn, ast.Name):
            return fn.id
        if isinstance(fn, ast.Attribute):
            return fn.attr
        return ""

    def _index(self, info: _FileInfo) -> None:
        from .pallas_budget import _module_consts

        info.consts = _module_consts(info.tree)
        stack: List[ast.AST] = []

        def walk(node, fn):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                info.funcs.setdefault(node.name, node)
                self.defs.setdefault(node.name, []).append((info, node))
                fn = node
            if isinstance(node, ast.Call):
                name = self._callee(node)
                if name:
                    self.calls.setdefault(name, []).append(
                        (info, node, fn))
            for child in ast.iter_child_nodes(node):
                walk(child, fn)

        walk(info.tree, None)

    def def_of(self, name: str,
               prefer: _FileInfo) -> Optional[Tuple[_FileInfo, ast.AST]]:
        cands = self.defs.get(name, [])
        same = [c for c in cands if c[0] is prefer]
        if same:
            return same[0]
        if len(cands) == 1:          # unambiguous across the repo
            return cands[0]
        return None                  # ambiguous: stay silent


def _classify(expr: ast.AST, info: _FileInfo,
              fn: Optional[ast.AST], graph: _Graph,
              depth: int, visited: set):
    """(verdict, detail, anchor_line) for a shape-valued expression.
    RAW means PROVABLY unbucketed; UNKNOWN means out of AST reach
    (silent — the runtime guard is the backstop)."""
    if depth > _MAX_DEPTH:
        return UNKNOWN, "", 0
    if isinstance(expr, ast.Constant):
        v = expr.value
        if v is None:
            return BUCKETED, "", 0       # no-floor sentinel
        if isinstance(v, bool) or not isinstance(v, int):
            return UNKNOWN, "", 0
        if v == 0 or _is_pow2(v):
            return BUCKETED, "", 0       # 0 = no-floor sentinel
        return RAW, f"literal {v} is not a power of two", expr.lineno
    if isinstance(expr, ast.Call):
        name = _Graph._callee(expr)
        if name in SANCTIONERS:
            return BUCKETED, "", 0
        if name == "len":
            return RAW, "a raw len(...) reaches the jit boundary", \
                expr.lineno
        if name in ("min", "max"):
            verdicts = [_classify(a, info, fn, graph, depth + 1,
                                  visited) for a in expr.args]
            if any(v[0] == RAW for v in verdicts):
                return next(v for v in verdicts if v[0] == RAW)
            if verdicts and all(v[0] == BUCKETED for v in verdicts):
                return BUCKETED, "", 0
        return UNKNOWN, "", 0
    if isinstance(expr, ast.Attribute):
        if expr.attr in RAW_ATTRS:
            return RAW, f"raw memo count .{expr.attr} reaches the " \
                "jit boundary (one program per distinct history " \
                "shape)", expr.lineno
        if expr.attr in BUCKETED_ATTRS:
            return BUCKETED, "", 0
        return UNKNOWN, "", 0
    if isinstance(expr, ast.Subscript):
        base = expr.value
        if isinstance(base, ast.Attribute) and base.attr == "shape":
            return RAW, "raw .shape[...] reaches the jit boundary " \
                "unbucketed", expr.lineno
        return UNKNOWN, "", 0
    if isinstance(expr, (ast.BoolOp, ast.IfExp)):
        parts = (expr.values if isinstance(expr, ast.BoolOp)
                 else [expr.body, expr.orelse])
        verdicts = [_classify(p, info, fn, graph, depth + 1, visited)
                    for p in parts]
        for v in verdicts:
            if v[0] == RAW:
                return v
        if verdicts and all(v[0] == BUCKETED for v in verdicts):
            return BUCKETED, "", 0
        return UNKNOWN, "", 0
    if isinstance(expr, ast.BinOp):
        for side in (expr.left, expr.right):
            v = _classify(side, info, fn, graph, depth + 1, visited)
            if v[0] == RAW:
                return v
        return UNKNOWN, "", 0
    if isinstance(expr, ast.Name):
        return _classify_name(expr.id, getattr(expr, "lineno", 0),
                              info, fn, graph, depth, visited)
    return UNKNOWN, "", 0


def _classify_name(name: str, use_line: int, info: _FileInfo,
                   fn: Optional[ast.AST], graph: _Graph, depth: int,
                   visited: set):
    # the LAST local assignment dominating the use site wins — the
    # first-match rule both flagged `n = len(xs); n = next_pow2(n)`
    # and waved through the reversed order
    if fn is not None:
        best = None
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == name \
                    and node.lineno < use_line \
                    and (best is None or node.lineno > best.lineno):
                best = node
        if best is not None:
            return _classify(best.value, info, fn, graph,
                             depth + 1, visited)
        # a parameter: chase every call site of the enclosing function
        args = getattr(fn, "args", None)
        if args is not None:
            names = [a.arg for a in args.args]
            if name in names:
                return _chase_param(fn, name, names.index(name),
                                    graph, depth, visited)
    if name in info.consts:
        v = info.consts[name]
        if v == 0 or _is_pow2(v):
            return BUCKETED, "", 0
        return RAW, f"module constant {name}={v} is not a power of " \
            "two", 0
    return UNKNOWN, "", 0


def _chase_param(fn: ast.AST, param: str, pos: int, graph: _Graph,
                 depth: int, visited: set):
    """Interprocedural step: classify the argument bound to ``param``
    at every call site of ``fn``. A single provably-raw call site
    makes the parameter RAW (anchored at that call site)."""
    key = (id(fn), param)
    if key in visited or depth > _MAX_DEPTH:
        return UNKNOWN, "", 0
    visited = visited | {key}
    sites = graph.calls.get(fn.name, [])
    if not sites:
        return UNKNOWN, "", 0
    defaults = getattr(fn, "args", None)
    n_pos = len(defaults.args) if defaults is not None else 0
    is_method = (defaults is not None and defaults.args
                 and defaults.args[0].arg in ("self", "cls"))
    any_unknown = not sites
    all_bucketed = bool(sites)
    for cinfo, call, cfn in sites:
        arg_expr = None
        for kw in call.keywords:
            if kw.arg == param:
                arg_expr = kw.value
        # positional mapping: methods called through an attribute drop
        # the self slot; other method call forms make no claim
        cpos = pos
        if is_method:
            if not isinstance(call.func, ast.Attribute):
                cpos = -1
            else:
                cpos = pos - 1
        if arg_expr is None and 0 <= cpos < len(call.args) \
                and not any(isinstance(a, ast.Starred)
                            for a in call.args[:cpos + 1]) \
                and pos < n_pos:
            arg_expr = call.args[cpos]
        if arg_expr is None:
            any_unknown = True       # default value / splat: no claim
            all_bucketed = False
            continue
        v, detail, anchor = _classify(arg_expr, cinfo, cfn, graph,
                                      depth + 1, visited)
        if v == RAW:
            return RAW, f"{detail} (via {fn.name}({param}=...) at " \
                f"{os.path.basename(cinfo.path)}:" \
                f"{anchor or call.lineno})", anchor or call.lineno
        if v != BUCKETED:
            any_unknown = True
            all_bucketed = False
    if all_bucketed and not any_unknown:
        return BUCKETED, "", 0
    return UNKNOWN, "", 0


def scan_files(paths: Sequence[str], *,
               apply_suppressions: bool = True) -> List[Finding]:
    """The ``unbucketed-dispatch-site`` rule over a file set (the
    call graph is built over exactly these files)."""
    infos: List[_FileInfo] = []
    for p in paths:
        if not os.path.exists(p):
            continue
        try:
            with open(p, encoding="utf-8") as fh:
                src = fh.read()
            tree = ast.parse(src, filename=p)
        except (OSError, SyntaxError):
            continue                 # lint owns syntax errors
        infos.append(_FileInfo(path=p, tree=tree,
                               lines=src.splitlines()))
    graph = _Graph(infos)
    out: List[Finding] = []
    for info in infos:
        for name, spec in SHAPE_SINKS.items():
            for cinfo, call, cfn in graph.calls.get(name, []):
                if cinfo is not info:
                    continue
                exprs: List[Tuple[str, ast.AST]] = []
                for kw in call.keywords:
                    if kw.arg in spec.get("kwargs", ()):
                        exprs.append((kw.arg, kw.value))
                for pos in spec.get("pos", ()):
                    if pos < len(call.args) and not any(
                            isinstance(a, ast.Starred)
                            for a in call.args[:pos + 1]):
                        exprs.append((f"arg{pos}", call.args[pos]))
                for argname, expr in exprs:
                    v, detail, anchor = _classify(
                        expr, info, cfn, graph, 0, set())
                    if v != RAW:
                        continue
                    line = call.lineno
                    out.append(Finding(
                        "unbucketed-dispatch-site", info.path, line,
                        f"{name}({argname}=...): {detail} — every "
                        "jit-visible shape must come from a declared "
                        "ladder (next_pow2 / service bucket / kernel "
                        "spec); an unbucketed shape compiles one "
                        "program per seed and can OOM LLVM"))
    if not apply_suppressions:
        return out
    # suppressions apply at the sink line
    by_path = {info.path: info.lines for info in infos}
    return [f for f in out
            if not suppressed(by_path.get(f.path, ()), f.line,
                              f.rule)]


__all__ = ["Axis", "Inventory", "Site", "SHAPE_SINKS",
           "static_inventory", "render_programs", "scan_files",
           "trace_witnesses", "witness_table"]


from . import Pass, filter_suppressed, register_pass


def _repo_stage(ctx):
    # raw once: the stage filters suppressions itself and deposits
    # the raw findings for the stale-suppression audit (one
    # call-graph build per run, not two)
    raw = scan_files(ctx["prod"], apply_suppressions=False)
    ctx["raw"]["compile-surface"] = raw
    out = filter_suppressed(raw)
    if ctx["trace"]:
        out += trace_witnesses()
    return out


register_pass(Pass(
    name="compile-surface",
    scan_paths=scan_files,
    raw_paths=lambda paths: scan_files(paths,
                                       apply_suppressions=False),
    repo_stage=_repo_stage,
))
