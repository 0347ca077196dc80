"""Tier-1: the compile-surface prover (analysis pass 4) and the
runtime compile guard.

Contracts:

- the committed ``PROGRAMS.md`` inventory artifact matches the
  generated one (drift = failure — same pattern as the budget table);
- a mixed-shape ``check_batch`` + shrink + txn workload run under the
  compile guard observes ONLY programs inside the static inventory;
- a deliberately unbucketed shape driven through a monitored engine
  entry IS caught as an offender;
- the ``unbucketed-dispatch-site`` rule chases shape values through
  the call graph (the seeded fixture's raw ``memo.n_states`` is
  laundered through a helper);
- the ``stale-suppression`` audit flags dead markers and keeps live
  ones.
"""
from __future__ import annotations

import os
import random

import numpy as np
import pytest

from comdb2_tpu import analysis
from comdb2_tpu.analysis import compile_surface as CS
from comdb2_tpu.utils import compile_guard as CG

REPO = analysis.repo_root()
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")


# --- static inventory --------------------------------------------------------

def test_programs_artifact_matches_committed():
    """The checked-in PROGRAMS.md is exactly what the prover
    generates — regenerating it is the fix when ladders change:
    ``python -m comdb2_tpu.analysis --programs PROGRAMS.md``."""
    committed = open(os.path.join(REPO, "PROGRAMS.md")).read()
    assert CS.render_programs() == committed, \
        "PROGRAMS.md drifted from the declared ladders — regenerate " \
        "with: python -m comdb2_tpu.analysis --programs PROGRAMS.md"


def test_inventory_covers_every_engine_surface():
    inv = CS.static_inventory()
    for name in ("seg_scan", "seg_stream_scan", "seg_chunk",
                 "check_device_keys", "check_device_seg_batch",
                 "check_device_batch",
                 "check_device_seg2", "closure_diag_kernel"):
        assert inv.site_for(name) is not None, name


def test_inventory_matching():
    inv = CS.static_inventory()

    def rec(name, *shapes):
        return CG.CompileRecord(name=name, shapes=shapes,
                                dtypes=("int32",) * len(shapes))

    # a bucketed keys-engine signature is inside the surface
    ok = rec("check_device_keys", (16, 16), (8, 4, 2), (8, 4, 2),
             (8, 4), (8,))
    assert inv.matches(ok)
    # the same signature with a non-pow2 table dim is an offender
    bad = rec("check_device_keys", (24, 24), (8, 4, 2), (8, 4, 2),
              (8, 4), (8,))
    assert not inv.matches(bad)
    # closure bucket; then a non-pow2 N
    assert inv.matches(rec("closure_diag_kernel", (4, 64, 8)))
    assert not inv.matches(rec("closure_diag_kernel", (4, 24, 3)))
    # an unknown jit name is outside the surface unless infra-listed
    assert not inv.matches(rec("rogue_engine", (1000, 1000)))
    assert inv.matches(rec("convert_element_type", ()))
    assert inv.offenders([ok, bad]) == [bad]


def test_witnesses_trace_clean():
    """Every ladder witness still traces through the real entry
    points (jax.eval_shape — no compile)."""
    findings = CS.trace_witnesses()
    assert findings == [], [f.format() for f in findings]


# --- runtime guard -----------------------------------------------------------

def test_parse_compile_log():
    rec = CG.parse_compile_log(
        "Compiling jit(check_device_keys) with global shapes and types "
        "(ShapedArray(int32[16,16]), ShapedArray(int32[8,4,2]), "
        "ShapedArray(int32[], weak_type=True)). Argument mapping: "
        "(UnspecifiedValue, UnspecifiedValue, UnspecifiedValue).")
    assert rec is not None
    assert rec.name == "check_device_keys"
    assert rec.shapes == ((16, 16), (8, 4, 2), ())
    assert rec.dtypes == ("int32", "int32", "int32")
    assert CG.parse_compile_log("Finished tracing foo") is None


def test_parse_compile_log_live_lowering():
    """The parser reads what the INSTALLED jax logs, not a remembered
    format: lower a fresh shape under the guard and find it."""
    import jax
    import jax.numpy as jnp

    def guard_probe(x, y):
        return x @ x + y

    with CG.guard() as g:
        jax.jit(guard_probe)(jnp.ones((24, 24), jnp.float32),
                             jnp.ones((24,), jnp.int8))
    recs = [r for r in g.records if r.name == "guard_probe"]
    assert len(recs) == 1, [r.format() for r in g.records]
    assert recs[0].shapes == ((24, 24), (24,))
    assert recs[0].dtypes == ("float32", "int8")


def test_carry_reset_lowers_with_its_declared_signature():
    """The donated-carry recycle program keeps its (ws, stat)
    parameters: jax prunes unused arguments by default, and the pruned
    ``carry_reset()`` escaped the inventory on the chip (PR 21)."""
    import jax.numpy as jnp

    from comdb2_tpu.checker import pallas_seg as PS

    spec = PS.spec_for(8, 32, 4, 2)
    reset = PS._reset_fn(spec, 8)
    with CG.guard() as g:
        ws = tuple(jnp.asarray(w) for w in PS.initial_frontier(spec))
        reset(ws, jnp.asarray(PS._init_stat()))
    recs = [r for r in g.records if r.name == "carry_reset"]
    assert [r.shapes for r in recs] == [
        ((PS.carry_rows(spec), 128),) * spec.n_words + ((1, 128),)]
    assert not g.offenders(CS.static_inventory())


def test_guard_mixed_workload_stays_inside_inventory():
    """The acceptance workload: mixed-shape check_batch + shrink +
    txn closure under the guard — observed compiles ⊆ static
    inventory."""
    from engine_oracles import last_resort_nt
    from comdb2_tpu.checker.batch import check_batch, pack_batch
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops import op as O
    from comdb2_tpu.ops.synth import register_history
    from comdb2_tpu.shrink import Shrinker
    from comdb2_tpu.txn import closure_jax as CJ
    from comdb2_tpu.utils import next_pow2

    inv = CS.static_inventory()
    rng = random.Random(7)
    # closure programs are cached per process: another test file on
    # this worker may already have built the N buckets used below
    CJ._JITTED.clear()
    with CG.guard() as g:
        # two shape buckets through the batched XLA engines
        for n_ev, B in ((24, 4), (48, 8)):
            hs = [register_history(rng, n_procs=3, n_events=n_ev,
                                   p_info=0.0) for _ in range(B)]
            batch = pack_batch(hs, cas_register())
            ns = next_pow2(batch.memo.n_states)
            nt = next_pow2(batch.memo.n_transitions)
            # the keys engine, then a declared table too wide for
            # its key: the last resort
            for engine, nt_pad in (("keys", nt),
                                   ("vmap", last_resort_nt(batch))):
                info: dict = {}
                status, _, _ = check_batch(
                    batch, F=64, s_pad=8, k_pad=2, n_states_pad=ns,
                    n_transitions_pad=nt_pad, info=info)
                assert info["engine"] == engine
                assert (np.asarray(status) == 0).all()
        # shrink: pow2 kept-op buckets through check_batch
        seed = [O.invoke(0, "write", 1), O.ok(0, "write", 1),
                O.invoke(1, "write", 2), O.ok(1, "write", 2),
                O.invoke(2, "read", None), O.Op(2, "ok", "read", 1)]
        for _ in range(8):
            seed += [O.invoke(3, "write", 3), O.ok(3, "write", 3)]
        job = Shrinker(seed, "cas-register", F=64)
        steps = 0
        while not job.step() and steps < 32:
            steps += 1
        assert job.error is None
        # txn closure: two N buckets, single and batched
        CJ.closure_diag(np.zeros((4, 16, 16), bool))
        CJ.closure_diag_batch(np.zeros((2, 4, 32, 32), bool))

    off = g.offenders(inv)
    assert off == [], [r.format() for r in off]
    g.assert_closed(inv)            # the raising form agrees
    c = g.counters()
    # the cleared cache rebuilt the N=16 and N=32 programs
    assert c["closure_programs"] >= 2
    assert c["xla_lowerings"] >= 4  # at least the 2x2 engine programs
    assert any(r.name == "closure_diag_kernel" for r in g.records)


def test_guard_catches_deliberately_unbucketed_shape():
    from comdb2_tpu.checker import linear_jax as LJ

    inv = CS.static_inventory()
    with CG.guard() as g:
        succ = np.full((24, 24), -1, np.int32)    # 24: not a pow2
        ip = np.full((8, 4, 2), -1, np.int32)
        it = np.zeros((8, 4, 2), np.int32)
        okp = np.full((8, 4), -1, np.int32)
        dp = np.zeros(8, np.int32)
        LJ.check_device_keys(succ, ip, it, okp, dp, B=4, F=64, P=2,
                             n_states=24, n_transitions=24)
    off = g.offenders(inv)
    assert any(r.name == "check_device_keys" and (24, 24) in r.shapes
               for r in off), [r.format() for r in g.records]
    with pytest.raises(CG.CompileSurfaceError):
        g.assert_closed(inv)


# --- the unbucketed-dispatch-site rule ---------------------------------------

def test_unbucketed_rule_is_interprocedural():
    path = os.path.join(FIXTURES, "bad_unbucketed_dispatch.py")
    findings = CS.scan_files([path])
    rules = {f.rule for f in findings}
    assert rules == {"unbucketed-dispatch-site"}
    msgs = " ".join(f.message for f in findings)
    # the helper-laundered raw memo count is chased to its call site
    assert "via _dispatch" in msgs
    # the direct len(...) case is caught without the chase
    assert "len(" in msgs


def test_unbucketed_rule_accepts_sanctioned_values():
    src = (
        "from comdb2_tpu.checker.batch import check_batch\n"
        "from comdb2_tpu.utils import next_pow2\n"
        "def serve(batch, items):\n"
        "    return check_batch(batch, s_pad=64,\n"
        "                       n_states_pad=next_pow2(len(items)))\n")
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "ok_site.py")
        with open(p, "w") as fh:
            fh.write(src)
        assert CS.scan_files([p]) == []


def test_unbucketed_rule_uses_last_dominating_assignment(tmp_path):
    """Reassignment resolves to the LAST assignment before the sink,
    in both directions: sanitizing a raw value clears the finding,
    and re-rawing a sanctioned name flags."""
    clean = tmp_path / "clean.py"
    clean.write_text(
        "from comdb2_tpu.checker.batch import check_batch\n"
        "from comdb2_tpu.utils import next_pow2\n"
        "def serve(batch, items):\n"
        "    n = len(items)\n"
        "    n = next_pow2(n)\n"
        "    return check_batch(batch, s_pad=n)\n")
    assert CS.scan_files([str(clean)]) == []
    rawed = tmp_path / "rawed.py"
    rawed.write_text(
        "from comdb2_tpu.checker.batch import check_batch\n"
        "from comdb2_tpu.utils import next_pow2\n"
        "def serve(batch, items):\n"
        "    n = next_pow2(8)\n"
        "    n = len(items)\n"
        "    return check_batch(batch, s_pad=n)\n")
    assert [f.rule for f in CS.scan_files([str(rawed)])] \
        == ["unbucketed-dispatch-site"]


def test_unbucketed_rule_suppressible():
    src = (
        "from comdb2_tpu.checker.batch import check_batch\n"
        "def serve(batch, items):\n"
        "    return check_batch(batch, s_pad=len(items))"
        "  # analysis: ignore[unbucketed-dispatch-site]\n")
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "sup_site.py")
        with open(p, "w") as fh:
            fh.write(src)
        assert CS.scan_files([p]) == []
        assert CS.scan_files([p], apply_suppressions=False) != []


# --- stale-suppression audit -------------------------------------------------

def test_stale_suppression_fixture():
    path = os.path.join(FIXTURES, "bad_stale_suppression.py")
    findings = analysis.audit_suppressions([path])
    assert [f.rule for f in findings] == ["stale-suppression"]
    assert "hash-dedup" in findings[0].message


def test_live_suppression_not_flagged(tmp_path):
    # a marker whose rule DOES trip on its line is live, not stale
    live = tmp_path / "live.py"
    live.write_text(
        "import os\nimport jax\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'"
        "  # analysis: ignore[jax-env-after-import]\n")
    assert analysis.audit_suppressions([str(live)]) == []


def test_marker_text_in_string_literal_is_not_a_marker(tmp_path):
    # prose mentioning the marker (docstrings, test sources) must not
    # be audited as a suppression — only real comments count
    prose = tmp_path / "prose.py"
    prose.write_text(
        'DOC = "append # analysis: ignore[hash-dedup] to the line"\n')
    assert analysis.audit_suppressions([str(prose)]) == []


def test_blanket_stale_marker_cannot_self_suppress(tmp_path):
    # a blanket marker on a clean line is stale even though blanket
    # markers suppress every OTHER rule on their line
    f = tmp_path / "blanket.py"
    f.write_text("x = 1  # analysis: ignore\n")
    findings = analysis.audit_suppressions([str(f)])
    assert [f_.rule for f_ in findings] == ["stale-suppression"]
