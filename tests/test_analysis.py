"""Tier-1: the repo-wide static invariant checker.

Three contracts:

- ``python -m comdb2_tpu.analysis`` exits 0 on the repo at HEAD — every
  future PR passes the checker by construction;
- each seeded violation fixture (tests/fixtures/analysis/) makes it
  exit non-zero naming the expected rule id with a ``file:line`` anchor;
- the budget analyzer's golden contract: every production ``spec_for``
  tier is accepted, and the known-bad configs (2048-step grid, 2048x10
  prefetch, non-(8,128) block, K=9) are rejected.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from comdb2_tpu import analysis
from comdb2_tpu.analysis import (dataflow, jaxpr_audit, lifecycle, lint,
                                 pallas_budget)

REPO = analysis.repo_root()
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analysis")

#: fixture -> rule id it must trip (mirrors fixtures/analysis/README.md)
FIXTURE_RULES = {
    "bad_env_jax.py": "jax-env-after-import",
    "bad_multiprocessing.py": "no-multiprocessing",
    "bad_hash_dedup.py": "hash-dedup",
    "bad_dup_cond.py": "dup-cond-closure",
    "bad_keyed_history.py": "keyed-history-wrap",
    "bad_nemesis_completion.py": "nemesis-info-completion",
    "bad_dispatch_loop.py": "per-item-dispatch",
    "bad_txn_dispatch_loop.py": "per-item-dispatch",
    "bad_shrink_dispatch_loop.py": "per-item-dispatch",
    "bad_pack_per_op_loop.py": "per-op-host-loop",
    "bad_pallas_grid.py": "pallas-grid-steps",
    "bad_pallas_prefetch.py": "pallas-prefetch-smem",
    "bad_pallas_block.py": "pallas-block-shape",
    "bad_pallas_k9.py": "pallas-k-cap",
    "bad_unbucketed_shape.py": "jaxpr-unbucketed-shape",
    "bad_unbucketed_dispatch.py": "unbucketed-dispatch-site",
    "bad_mxu_unbucketed_dispatch.py": "unbucketed-dispatch-site",
    "bad_stream_unbucketed_delta.py": "unbucketed-dispatch-site",
    "bad_stream_megabatch_delta.py": "unbucketed-dispatch-site",
    "bad_wl_unbucketed_dispatch.py": "unbucketed-dispatch-site",
    "bad_stream_jnp_checkpoint.py": "host-numpy-checkpoint",
    "bad_unsharded_mesh_dispatch.py": "unbucketed-dispatch-site",
    "bad_vmap_sharded_route.py": "vmap-sharded-oracle",
    "bad_stale_suppression.py": "stale-suppression",
    "bad_raw_clock_dispatch.py": "raw-clock-in-pipeline",
    "bad_ready_before_publish.py": "publish-before-ready",
    "bad_close_before_deregister.py": "deregister-before-close",
    "bad_log_before_success.py": "log-after-success",
    "bad_leaked_pin.py": "release-in-finally",
    "bad_stale_ttl_timestamp.py": "fresh-deadline-timestamp",
    "bad_kill_no_wait.py": "wait-after-kill",
    "bad_sync_readback_pump.py": "sync-readback-in-pump",
    "bad_per_item_transfer.py": "per-item-transfer",
}


def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "comdb2_tpu.analysis", *argv],
        cwd=REPO, capture_output=True, text=True, timeout=300)


# --- the repo itself is clean ------------------------------------------------

def test_repo_scan_is_clean():
    """The acceptance gate: the checker exits 0 on the repo at HEAD
    (full run — lint, production budgets, jaxpr audit incl. the
    abstract traces)."""
    r = _run_cli()
    assert r.returncode == 0, \
        f"checker found violations at HEAD:\n{r.stdout}{r.stderr}"
    assert "OK: 0 findings" in r.stdout


# --- every seeded fixture fails with the right rule --------------------------

def test_fixture_inventory_matches_readme():
    on_disk = {f for f in os.listdir(FIXTURES) if f.endswith(".py")}
    assert on_disk == set(FIXTURE_RULES), \
        "fixtures/analysis/ and FIXTURE_RULES drifted apart"
    # the acceptance floor: >= 30 fixtures across the pass families
    assert len(FIXTURE_RULES) >= 30


@pytest.mark.parametrize("fixture,rule", sorted(FIXTURE_RULES.items()))
def test_fixture_trips_rule(fixture, rule):
    path = os.path.join(FIXTURES, fixture)
    r = _run_cli(path)
    assert r.returncode != 0, f"{fixture} passed the checker"
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith(rule + " ")), None)
    assert line is not None, \
        f"{fixture}: no {rule} finding in:\n{r.stdout}"
    # file:line anchor present and parseable
    loc = line.split(" ", 2)[1]
    fpath, _, lineno = loc.rpartition(":")
    assert fpath.endswith(fixture) and int(lineno) > 0


def test_fixtures_excluded_from_repo_scan():
    files = analysis.collect_files()
    assert files and not any("fixtures" in f for f in files)


def test_hash_dedup_rule_covers_mxu_module():
    """checker/mxu.py imports jax, so the hash-dedup rule is ACTIVE
    there: a hash() snuck into the new engine's dedup path would be a
    finding (the rule keys on the jax import, not a module list — this
    pins that the new engine didn't fall outside it), and the module
    as committed is clean."""
    path = os.path.join(REPO, "comdb2_tpu", "checker", "mxu.py")
    with open(path) as fh:
        src = fh.read()
    seeded = lint.lint_file(path, source=src + "\n_bad = hash((1, 2))\n")
    assert any(f.rule == "hash-dedup" for f in seeded)
    assert [f.format() for f in lint.lint_file(path, source=src)] == []


# --- budget analyzer golden tests --------------------------------------------

def test_budget_accepts_every_production_tier():
    tiers = pallas_budget.production_tiers()
    assert tiers, "no spec_for tier reachable from the bucket ladder"
    for bucket, P, K, spec in tiers:
        findings = pallas_budget.check_spec(
            spec, where=f"spec_for({bucket},P={P},K={K})")
        assert findings == [], [f.format() for f in findings]
    assert pallas_budget.check_production() == []


@pytest.mark.parametrize("cfg,rule", [
    (dict(grid_steps=2048), "pallas-grid-steps"),
    (dict(prefetch_int32=2048 * 10), "pallas-prefetch-smem"),
    (dict(block=(8, 100)), "pallas-block-shape"),
    (dict(block=(3, 128)), "pallas-block-shape"),
    (dict(K=9), "pallas-k-cap"),
    (dict(F=64), "pallas-f-cap"),
    (dict(f_top=8192), "pallas-f-cap"),        # past the top rung cap
    (dict(f_top=3072), "pallas-f-cap"),        # not a doubling of 128
    (dict(vmem_bytes=32 << 20), "pallas-vmem-budget"),
])
def test_budget_rejects_known_bad(cfg, rule):
    findings = pallas_budget.check_config(**cfg)
    assert findings and findings[0].rule == rule


@pytest.mark.parametrize("cfg", [
    dict(grid_steps=1024),          # production CHUNK
    dict(grid_steps=1408),          # measured compile bound
    dict(prefetch_int32=1024 * 10),
    dict(block=(8, 128)),
    dict(block=(16, 128)),
    dict(K=8, F=128),
    dict(f_top=4096),               # the production top rung
    dict(f_top=256, vmem_bytes=8 << 20),
])
def test_budget_accepts_known_good(cfg):
    assert pallas_budget.check_config(**cfg) == []


def test_budget_grid_steps_are_the_dim_product():
    """Grid steps run sequentially, so the Mosaic bound applies to the
    PRODUCT of the grid dims — a (64, 64) grid is 4096 steps and must
    be flagged even though each dim alone is tiny."""
    src = ("from jax.experimental import pallas as pl\n"
           "def run(k, x):\n"
           "    return pl.pallas_call(k, grid=(64, 64))(x)\n")
    fs = pallas_budget.scan_file("<mem>", src)
    assert [f.rule for f in fs] == ["pallas-grid-steps"]
    assert pallas_budget.scan_file(
        "<mem>", src.replace("(64, 64)", "(8, 128)")) == []


def test_budget_counts_the_wide_rungs_vmem():
    """Every production tier's kernel fits the scoped VMEM budget with
    the top rung's sort buffer; a (16,128)-tier kernel asks the
    most."""
    from comdb2_tpu.checker import pallas_seg as PS

    tiers = pallas_budget.production_tiers()
    sizes = {spec: pallas_budget.kernel_vmem_bytes(spec)
             for _, _, _, spec in tiers}
    assert max(sizes.values()) <= pallas_budget.VMEM_BUDGET_BYTES
    widest = max(sizes, key=sizes.get)
    assert widest.rows == 2 * PS.ROWS
    assert pallas_budget.check_production() == []
    # per key word: rung 0's 16 rows, the wide frontier's 32 and the
    # top rung's 64-block sort buffer at P = 10; no entry copy
    spec = PS.spec_for(8, 14, 10, 8)
    assert PS.scratch_rows(spec) == (16, 32, 512)
    row = PS.LANES * 4
    blocks = row * (2 * 2 * (16 + 32) + 2 + 2 * 8 + spec.table_rows_pad)
    assert pallas_budget.kernel_vmem_bytes(spec, b_pad=8) == \
        2 * row * (16 + 32 + 512) + 2 * blocks


def test_budget_table_artifact():
    table = pallas_budget.budget_table()
    assert table.startswith("# Pallas budget table")
    # one row per distinct production tier (head, blank, 2 header rows)
    n_rows = len(table.splitlines()) - 4
    assert n_rows == len(pallas_budget.production_tiers())


# --- jaxpr audit -------------------------------------------------------------

def test_bucket_ladder_matches_fuzz_script():
    """PRODUCTION_BUCKETS mirrors scripts/fuzz_pallas_seg.py; the
    mirror must not drift (every fuzz `bucket = (a, b)` literal is in
    the ladder, checked by the AST scan being clean on the script)."""
    src = os.path.join(REPO, "scripts", "fuzz_pallas_seg.py")
    assert jaxpr_audit.scan_file(src) == []
    with open(src) as fh:
        text = fh.read()
    for ns, nt in pallas_budget.PRODUCTION_BUCKETS:
        assert f"({ns}, {nt})" in text, \
            f"bucket ({ns},{nt}) not exercised by the fuzz script"


def test_bucket_closure():
    assert jaxpr_audit.check_bucket_closure() == []


def test_trace_entry_points_clean():
    """Tracing the engine entry points across every declared bucket
    finds no duplicated cond sub-jaxprs (and traces successfully —
    a trace failure IS a finding)."""
    findings = jaxpr_audit.trace_entry_points()
    assert findings == [], [f.format() for f in findings]


def test_duplicated_cond_branches_detects():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(x):
        # non-trivial (>= MIN_BRANCH_EQNS equations), duplicated
        return jnp.sum(jnp.sin(x) * 2.0) + jnp.max(x)

    def f(x):
        # deliberately duplicated branch: the subject under test
        return lax.cond(x[0] > 0, body, body, x)  # analysis: ignore[dup-cond-closure]

    jaxpr = jax.make_jaxpr(f)(jnp.ones(8))
    assert jaxpr_audit.duplicated_cond_branches(jaxpr)


# --- suppression -------------------------------------------------------------

def test_per_line_suppression():
    src = ("import os\nimport jax\n"
           "os.environ['JAX_PLATFORMS'] = 'cpu'"
           "  # analysis: ignore[jax-env-after-import]\n")
    assert lint.lint_file("<mem>", src) == []
    # wrong rule id in the marker does NOT suppress
    src_wrong = src.replace("jax-env-after-import", "hash-dedup")
    assert [f.rule for f in lint.lint_file("<mem>", src_wrong)] == \
        ["jax-env-after-import"]
    # blanket marker suppresses everything on the line
    src_blanket = src.replace("[jax-env-after-import]", "")
    assert lint.lint_file("<mem>", src_blanket) == []


def test_cli_json_artifact(tmp_path):
    out = tmp_path / "findings.json"
    table = tmp_path / "budgets.md"
    r = _run_cli("--json", str(out), "--budget-table", str(table),
                 os.path.join(FIXTURES, "bad_pallas_k9.py"))
    assert r.returncode == 1
    import json
    data = json.loads(out.read_text())
    assert data and data[0]["rule"] == "pallas-k-cap"
    assert table.read_text().startswith("# Pallas budget table")


def test_cli_json_exit_code_regression(tmp_path):
    """``--json`` must not absorb the failure: findings still exit
    non-zero with the artifact written, and a clean file still exits
    zero (with an empty artifact)."""
    import json

    out = tmp_path / "findings.json"
    r = _run_cli("--json", str(out),
                 os.path.join(FIXTURES, "bad_multiprocessing.py"))
    assert r.returncode != 0
    assert json.loads(out.read_text())
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    out2 = tmp_path / "clean.json"
    r = _run_cli("--json", str(out2), str(clean))
    assert r.returncode == 0
    assert json.loads(out2.read_text()) == []


def test_cli_reports_per_pass_timing():
    """Slow passes must be visible: one timed line per pass on
    stderr."""
    r = _run_cli(os.path.join(FIXTURES, "bad_multiprocessing.py"))
    for name in ("lint", "pallas-budget", "jaxpr-audit",
                 "compile-surface", "lifecycle", "dataflow",
                 "suppression-audit"):
        assert f"pass {name}:" in r.stderr, r.stderr


def test_cli_programs_artifact(tmp_path):
    progs = tmp_path / "PROGRAMS.md"
    r = _run_cli("--programs", str(progs),
                 os.path.join(FIXTURES, "bad_multiprocessing.py"))
    assert r.returncode == 1            # the fixture still fails
    assert progs.read_text().startswith("# Compile-surface inventory")


# --- pass 5: lifecycle & dataflow ---------------------------------------------

#: the pass-5 rule ids (lifecycle + dataflow)
PASS5_RULES = {"publish-before-ready", "deregister-before-close",
               "log-after-success", "release-in-finally",
               "fresh-deadline-timestamp", "wait-after-kill",
               "sync-readback-in-pump", "per-item-transfer"}


def _pass5_rules(path):
    return ({f.rule for f in lifecycle.scan_file(path)}
            | {f.rule for f in dataflow.scan_files([path])})


@pytest.mark.parametrize("fixture,rule", sorted(FIXTURE_RULES.items()))
def test_pass5_rules_exclusive(fixture, rule):
    """The acceptance gate's exclusivity half: each pass-5 fixture
    trips exactly its own pass-5 rule, and NO pre-existing fixture
    trips any pass-5 rule (a cross-rule false positive on the seeded
    corpus would mean the new analyzers over-match)."""
    fired = _pass5_rules(os.path.join(FIXTURES, fixture))
    if rule in PASS5_RULES:
        assert fired == {rule}, (fixture, fired)
    else:
        assert fired == set(), (fixture, fired)


#: (tag, rule, pre-fix excerpt, post-fix excerpt) — the PR-12
#: review-round bugs, reproduced from the pre-fix code shape so the
#: rules provably catch what the reviews caught by hand
PR12_EXCERPTS = [
    ("shutdown-close-order", "deregister-before-close",
     # service/daemon.py pre-fix: listener closed before the withdraw
     '''
class D:
    def _shutdown(self):
        for p, reply in self.core.tick(monotonic()):
            self._send(p.ctx, reply)
        self._lsock.close()
        self._sel.close()
        self._pmux_withdraw()
''',
     '''
class D:
    def _shutdown(self):
        self._pmux_withdraw()
        for p, reply in self.core.tick(monotonic()):
            self._send(p.ctx, reply)
        self._lsock.close()
        self._sel.close()
'''),
    ("memo-log-order", "log-after-success",
     # models/memo.py pre-fix: the extend-call log appended BEFORE the
     # closure ran — a MemoOverflow mid-extend poisoned every restore
     '''
class IncrementalMemo:
    def extend(self, ops):
        self._log.append(tuple(ops))
        self._closure(ops)
        self._depth += len(ops)
''',
     '''
class IncrementalMemo:
    def extend(self, ops):
        self._closure(ops)
        self._depth += len(ops)
        self._log.append(tuple(ops))
'''),
    ("stream-close-pin-leak", "release-in-finally",
     # client.py pre-fix: a close whose failover also failed leaked
     # the pin (the node's client parked in _parting forever)
     '''
class RoutedStream:
    def close(self):
        out = self._client.stream_close(self.sid)
        self._router._unpin(self._node)
        return out
''',
     '''
class RoutedStream:
    def close(self):
        try:
            out = self._client.stream_close(self.sid)
        finally:
            self._router._unpin(self._node)
        return out
'''),
    ("route-stale-ttl", "fresh-deadline-timestamp",
     # client.py pre-fix: blacklist TTL anchored at walk start — a
     # hung connect burned the timeout, so the deadline was already
     # expired when written and the node got re-dialed hot
     '''
def _route(self, cls):
    now = monotonic()
    for name in self._ring.walk(cls):
        try:
            return self._dial(name)
        except OSError:
            self._blacklist[name] = now + self.blacklist_ttl_s
    return None
''',
     '''
def _route(self, cls):
    for name in self._ring.walk(cls):
        try:
            return self._dial(name)
        except OSError:
            self._blacklist[name] = monotonic() + self.blacklist_ttl_s
    return None
'''),
]


@pytest.mark.parametrize("tag,rule,bad,good",
                         PR12_EXCERPTS,
                         ids=[e[0] for e in PR12_EXCERPTS])
def test_pass5_reproduces_pr12_review_bugs(tag, rule, bad, good):
    """The acceptance gate's reproduction half: reverting >= 3 of the
    PR-12 review-round fixes (as faithful pre-fix code excerpts) makes
    the matching rule fire, and each post-fix twin is clean — the
    rules encode exactly the orderings the reviews fixed by hand."""
    fired = [f.rule for f in lifecycle.scan_file("<mem>.py", bad)]
    assert fired == [rule], (tag, fired)
    assert lifecycle.scan_file("<mem>.py", good) == [], tag


def test_dataflow_deferred_finalize_exempt(tmp_path):
    """The ring's contract: readbacks in the DEFERRED finalize closure
    a hot path stages are the sanctioned pattern — only an inline
    readback on the beat itself is a finding."""
    inline = tmp_path / "inline_dispatch.py"
    inline.write_text(
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def pump(core):\n"
        "    out = jnp.sum(core.buf)\n"
        "    return np.asarray(out)\n")
    deferred = tmp_path / "deferred_dispatch.py"
    deferred.write_text(
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def pump(core):\n"
        "    out = jnp.sum(core.buf)\n"
        "    def finalize():\n"
        "        return np.asarray(out)\n"
        "    core.ring.append(finalize)\n")
    assert [f.rule for f in dataflow.scan_files([str(inline)])] == \
        ["sync-readback-in-pump"]
    assert dataflow.scan_files([str(deferred)]) == []


def test_pass5_suppression_live_and_stale(tmp_path):
    """The suppression-audit path for BOTH pass-5 analyzers: a live
    marker suppresses its finding without becoming stale (dataflow's
    whole-set raw_paths re-scan), and a marker on a clean line is a
    stale-suppression finding (lifecycle's per-file raw_file
    re-scan)."""
    live = tmp_path / "pump_dispatch.py"
    live.write_text(
        "import jax.numpy as jnp\n"
        "def pump(core):\n"
        "    x = jnp.sum(core.buf)\n"
        "    return float(x)"
        "  # analysis: ignore[sync-readback-in-pump]\n")
    stale = tmp_path / "svc_dispatch.py"
    stale.write_text(
        "def retire(proc):\n"
        "    proc.terminate()\n"
        "    proc.wait()  # analysis: ignore[wait-after-kill]\n")
    # the live marker suppresses, and the audit does not flag it
    assert analysis.run_paths([str(live)]) == []
    assert analysis.audit_suppressions([str(live)]) == []
    # the stale marker survives no rule and IS the finding
    fired = [f.rule for f in analysis.run_paths([str(stale)])]
    assert fired == ["stale-suppression"], fired


def test_pass5_json_exit_code(tmp_path):
    """``--json`` over a pass-5 fixture: non-zero exit with the rule
    in the artifact (the artifact records the failure, it never
    absorbs it)."""
    import json

    out = tmp_path / "findings.json"
    r = _run_cli("--json", str(out),
                 os.path.join(FIXTURES, "bad_sync_readback_pump.py"))
    assert r.returncode == 1
    rules = {f["rule"] for f in json.loads(out.read_text())}
    assert "sync-readback-in-pump" in rules, rules


# --- --changed incremental mode ----------------------------------------------

def _git(root, *args):
    subprocess.run(["git", "-c", "user.email=t@t.invalid",
                    "-c", "user.name=t", *args],
                   cwd=root, check=True, capture_output=True)


def test_changed_mode_agrees_with_full_run(tmp_path):
    """The acceptance gate: over a touched-file subset, the
    incremental ``--changed`` file set produces exactly the findings
    the full run attributes to those files — modified-tracked and
    untracked files are both in, committed-clean files are out."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "scripts"))
    _git(root, "init", "-q")
    clean = os.path.join(root, "scripts", "clean.py")
    with open(clean, "w") as fh:
        fh.write("x = 1\n")
    tracked = os.path.join(root, "scripts", "svc_dispatch.py")
    with open(tracked, "w") as fh:
        fh.write("def retire(p):\n    p.terminate()\n    p.wait()\n")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    # revert the wait-after-kill fix in the tracked file...
    with open(tracked, "w") as fh:
        fh.write("def retire(p):\n    p.terminate()\n")
    # ... and add an untracked file with a per-item transfer loop
    new = os.path.join(root, "scripts", "xfer_dispatch.py")
    with open(new, "w") as fh:
        fh.write("import jax\ndef push(items):\n"
                 "    for it in items:\n        jax.device_put(it)\n")
    changed = analysis.changed_files("HEAD", root=root)
    assert sorted(os.path.basename(p) for p in changed) == \
        ["svc_dispatch.py", "xfer_dispatch.py"]
    inc = {(os.path.basename(f.path), f.rule)
           for f in analysis.run_paths(changed)}
    full = {(os.path.basename(f.path), f.rule)
            for f in analysis.run_paths(analysis.collect_files(root))
            if f.path in set(changed)}
    assert inc == full == {("svc_dispatch.py", "wait-after-kill"),
                           ("xfer_dispatch.py", "per-item-transfer")}


def test_changed_cli_paths_and_bad_ref():
    """CLI wiring: ``--changed`` with explicit paths is an error, and
    an unresolvable ref exits 2 (distinct from the findings exit 1)."""
    r = _run_cli("--changed", "HEAD",
                 os.path.join(FIXTURES, "bad_multiprocessing.py"))
    assert r.returncode == 2
    r = _run_cli("--changed", "no-such-ref-xyz")
    assert r.returncode == 2
    assert "--changed" in r.stderr
