#!/usr/bin/env python3
"""Elastic-fleet nemesis gate: the checker pointed at its own serving
layer (docs/service.md "Elastic fleet").

The paper's thesis is that a distributed system earns trust only by
surviving injected faults while a checker watches — and our serving
layer is now a distributed system (supervised pmux-registered
daemons, ring-version epochs, checkpoint-migrating sessions). This
bench subjects it to its own medicine:

1. **kill-a-daemon-under-burst** — SIGKILL one of two daemons mid-
   burst (the harshest leave: no drain, no deregistration). The
   routed client must fail over (blacklist the corpse, refresh on
   the supervisor's stale-entry cleanup + epoch bump), the
   supervisor must reap the corpse (no zombies — no init reaper
   here) and respawn to the fleet floor.
2. **join-under-burst** — spawn an extra daemon mid-burst; the epoch
   bump must refresh the client ring and remap ≈1/N of the shape
   classes onto the newcomer (measured and gated — consistent
   hashing, never a reshuffle).
3. **session migration** — a streaming session's daemon is drained
   (`kind:"drain"`); the client hands the session off by checkpoint
   (O(carry)) and post-handoff appends must stay O(delta): dispatch
   deltas gated, zero replays.

Every client-observed request is recorded as an op pair — process =
request id, `invoke write [key 1]` at submission, `ok` at reply —
and the resulting fleet history is fed BACK through the surviving
fleet as a keyed check. The gate: every request answered exactly
once (a drop leaves a dangling invoke counted client-side; a
double-serve is a malformed second completion the checker itself
rejects), and the history checks VALID.

Honest accounting (CLAUDE.md): everything shares this container's
one CPU, so wall-clock is reported, never gated — the gates are
counts (answers, remaps, dispatches, zombies).

Usage: python scripts/bench_elastic.py
       [--requests-per-class 6] [--quick] [--out BENCH_elastic.json]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

SIZE_CLASSES = (10, 18, 30, 60, 140, 180)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def find_ct_pmux() -> str:
    """The pmux binary: a prior native build, or a direct g++ build
    (the same line scripts/check.sh falls back to)."""
    for cand in ("native/build/ct_pmux", "native/build-asan/ct_pmux"):
        p = os.path.join(REPO, cand)
        if os.path.exists(p):
            return p
    if shutil.which("g++") is None:
        raise SystemExit("no ct_pmux build and no g++ to make one")
    out = os.path.join(tempfile.mkdtemp(prefix="ct_pmux_"), "ct_pmux")
    subprocess.run(
        ["g++", "-O1", "-Wall", "-Inative/include",
         "native/src/pmux_main.cpp", "-o", out, "-lpthread"],
        cwd=REPO, check=True)
    return out


def start_pmux(binary: str, port: int):
    proc = subprocess.Popen([binary, "-p", str(port)],
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.2).close()
            return proc
        except OSError:
            time.sleep(0.1)
    proc.kill()
    proc.wait(timeout=30)   # no init reaper: reap before raising
    raise SystemExit("ct_pmux never came up")


DAEMON_ARGS = ["--backend", "cpu", "--no-prime", "--frontier", "64",
               "--fill-ms", "5"]


def zombies() -> int:
    """Unreaped children of THIS process. Scoped to our own pid
    because the gate means "the bench reaped everything it spawned" —
    a system-wide Z count is racy (LeakSanitizer's exit-time tracer
    briefly shows as a Z child of the dying ASan ct_pmux, which is
    the sanitizer runtime's corpse to collect, not ours)."""
    me = str(os.getpid())
    out = subprocess.run(["ps", "-eo", "ppid=,stat="],
                         capture_output=True, text=True).stdout
    return sum(1 for ln in out.splitlines()
               if ln.split()[:1] == [me]
               and ln.split()[1].startswith("Z"))


def req_history(i: int):
    """One request's op pair: its own key, one write — exactly-once
    serving is exactly one completion per invocation."""
    from comdb2_tpu.ops import op as O

    return (O.invoke(i, "write", (i, 1)), O.ok(i, "write", (i, 1)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests-per-class", type=int, default=6)
    ap.add_argument("--quick", action="store_true",
                    help="small run (the check.sh elastic stage)")
    ap.add_argument("--out",
                    default=os.path.join(REPO, "BENCH_elastic.json"))
    ap.add_argument("--max-remap", type=float, default=0.7,
                    help="gate on the join's remapped shape-class "
                         "fraction (expected ~1/3 at N=2->3)")
    args = ap.parse_args()
    if args.quick:
        args.requests_per_class = min(args.requests_per_class, 2)

    # backend discipline: every spawned daemon passes --backend cpu
    # (DAEMON_ARGS), which switches platforms through the config API
    # — the authoritative path; env vars after import do nothing
    # (CLAUDE.md). check.sh additionally exports JAX_PLATFORMS=cpu
    # for the subprocess tree.
    from comdb2_tpu.ops.history import history_to_edn
    from comdb2_tpu.ops.synth import register_history
    from comdb2_tpu.service.client import RoutedClient, ServiceError
    from comdb2_tpu.service.supervisor import Supervisor

    z0 = zombies()
    pmux_port = free_port()
    pmux = start_pmux(find_ct_pmux(), pmux_port)
    sup = Supervisor(pmux_port=pmux_port, min_daemons=2,
                     max_daemons=4, daemon_args=DAEMON_ARGS,
                     drain_grace_s=5.0, scale_cooldown_s=1e9)
    fleet_ops = []            # the client-observed serving history
    answered: dict = {}       # req id -> reply count
    failures: dict = {}       # req id -> error string
    out: dict = {"bench": "elastic", "backend": "cpu",
                 "size_classes": list(SIZE_CLASSES)}
    rc = None
    try:
        sup.spawn()
        sup.spawn()
        rc = RoutedClient.discover(pmux_port=pmux_port,
                                   timeout_s=300.0, retries=1,
                                   backoff_s=0.05)
        assert len(rc.clients) == 2, rc.clients
        epoch0 = rc.epoch

        texts = []
        for ci, n_events in enumerate(SIZE_CLASSES):
            for j in range(args.requests_per_class):
                h = register_history(
                    random.Random(9000 + 37 * ci + j), n_procs=3,
                    n_events=n_events, p_info=0.0)
                texts.append(history_to_edn(h))
        n = len(texts)

        def drive(i: int, text: str, route: str = "shape") -> None:
            """One request, recorded as the fleet history sees it."""
            inv, ok = req_history(i)
            fleet_ops.append(inv)
            try:
                r = rc.check(text, route=route)
            except (OSError, ServiceError) as e:
                # ServiceError: the whole walk ended overloaded /
                # shutting-down (overload_retries=0 under discover) —
                # record it as a gate failure, don't crash the bench
                failures[i] = str(e)
                return
            if r.get("ok"):
                answered[i] = answered.get(i, 0) + 1
                fleet_ops.append(ok)
            else:
                failures[i] = r.get("error", "?")

        # --- phase 1: kill a daemon mid-burst (SIGKILL nemesis) ----
        kill_at = n // 3
        victim = sup.children[0]
        served_before_kill = None
        for i, text in enumerate(texts):
            if i == kill_at:
                # the nemesis: SIGKILL with no drain and no reap HERE
                # — the supervisor's beat() poll()s and reaps the
                # corpse; waiting here would serialize the fault with
                # the burst we are measuring under
                victim.proc.kill()        # no drain, no deregister  # analysis: ignore[wait-after-kill]
                served_before_kill = dict(rc.served)
            drive(i, text)
            if i % 4 == 3:
                sup.beat()                # reap + stale cleanup +
                                          # respawn to the floor
        deadline = time.monotonic() + 30
        while len(sup.children) < 2 and time.monotonic() < deadline:
            sup.beat()
            time.sleep(0.2)
        out["kill"] = {
            "victim": victim.service,
            "killed_at_request": kill_at,
            "failovers": rc.failovers,
            "ring_refreshes": rc.refreshes,
            "stale_cleanups": sup.stale_cleanups,
            "deaths_reaped": sup.deaths,
            "respawned_to_floor": len(sup.children) >= 2,
        }
        # the survivor picked up the victim's classes: traffic kept
        # being served after the kill by SOMEONE else
        survivor = next(name for name in served_before_kill
                        if name != victim.service)
        assert rc.served[survivor] > served_before_kill[survivor], \
            "survivor served nothing after the kill"

        # --- phase 2: join under burst -----------------------------
        rc.maybe_refresh(force=True)
        # the remap bound is measured over a dense synthetic key set
        # (the live workload has only ~6 distinct shape classes —
        # far too few to estimate a fraction)
        probes = [f"probe|{i}" for i in range(512)]
        owners_before = {k: rc.ring.nodes_for(k)[0] for k in probes}
        joined = sup.spawn()              # registers + bumps epoch
        extra = []
        for ci, n_events in enumerate(SIZE_CLASSES):
            for j in range(args.requests_per_class):
                h = register_history(
                    random.Random(5000 + 31 * ci + j), n_procs=3,
                    n_events=n_events, p_info=0.0)
                extra.append(history_to_edn(h))
        for k, text in enumerate(extra):
            drive(n + k, text)
        n_total = n + len(extra)
        assert rc.epoch != epoch0, (epoch0, rc.epoch)
        owners_after = {k: rc.ring.nodes_for(k)[0] for k in probes}
        moved_keys = [k for k in probes
                      if owners_before[k] != owners_after[k]]
        remap_frac = len(moved_keys) / len(probes)
        # every moved key landed ON the newcomer (join never
        # shuffles keys between survivors)
        join_clean = all(owners_after[k] == joined.service
                         for k in moved_keys)
        # drive the newcomer for real: payload routing gives a dense
        # key space, so some recorded request provably hashes to it
        newcomer_serves = rc.served.get(joined.service, 0)
        probe_texts = [t for t in extra
                       if rc.ring.nodes_for(RoutedClient.route_key(
                           t, route="payload"))[0] == joined.service]
        for t in probe_texts[:4]:
            drive(n_total, t, route="payload")
            n_total += 1
        newcomer_serves = rc.served.get(joined.service, 0)
        out["join"] = {
            "service": joined.service,
            "epoch_before": epoch0, "epoch_after": rc.epoch,
            "remapped_fraction": round(remap_frac, 3),
            "moved_only_to_newcomer": join_clean,
            "max_remap_gate": args.max_remap,
            "newcomer_served": newcomer_serves,
        }

        # --- phase 3: stream-session migration via drain -----------
        sh = register_history(random.Random(77), n_procs=3,
                              n_events=96, p_info=0.0, max_pending=2)
        stream = rc.stream_open()
        cut = len(sh) // 2
        r1 = stream.append(sh[:cut])
        assert r1.get("ok") and r1["valid"] is True, r1
        d_half = r1["dispatches"]
        pinned = stream.node
        # drain the pinned daemon: the next append migrates by
        # checkpoint instead of replaying the retained deltas
        rc.clients[pinned].drain()
        time.sleep(0.3)                   # let its loop enter drain
        r2 = stream.append(sh[cut:])
        assert r2.get("ok") and r2["valid"] is True, r2
        closed = stream.close()
        out["stream"] = {
            "pinned": pinned, "migrated_to": stream.node,
            "migrations": stream.migrations,
            "replays_after_handoff": r2.get("replays", -1),
            "dispatches_first_half": d_half,
            "dispatches_total": r2["dispatches"],
            "final_valid": closed.get("valid"),
        }
        migration_ok = (
            stream.migrations == 1 and stream.node != pinned
            and r2.get("replays") == 0
            # O(delta): the second half costs about the first half —
            # a replay would re-dispatch the whole prefix on top
            and r2["dispatches"] - d_half <= d_half + 2
            and closed.get("valid") is True)
        # the drained daemon exits on its own; reap it and refill
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            sup.beat()
            if all(c.proc.poll() is None
                   for c in sup.children.values()) \
                    and len(sup.children) >= 2:
                break
            time.sleep(0.2)

        # --- the self-check gate -----------------------------------
        exactly_once = (len(answered) == n_total
                        and all(v == 1 for v in answered.values())
                        and not failures)
        edn = history_to_edn(fleet_ops)
        verdict = rc.check(edn, keyed=True,
                           raise_on_error=False)
        out["self_check"] = {
            "requests": n_total,
            "answered_exactly_once": exactly_once,
            "dropped": sorted(set(range(n_total)) - set(answered)),
            "failures": failures,
            "double_served": sorted(k for k, v in answered.items()
                                    if v > 1),
            "fleet_history_ops": len(fleet_ops),
            "fleet_history_valid": verdict.get("valid"),
            "checker_engine": verdict.get("engine"),
        }
        out["supervisor"] = {
            "spawned": sup.spawned, "retired": sup.retired,
            "deaths": sup.deaths,
            "stale_cleanups": sup.stale_cleanups,
        }
        gate_ok = (exactly_once
                   and verdict.get("valid") is True
                   and 0 < remap_frac <= args.max_remap
                   and join_clean
                   and newcomer_serves > 0
                   and migration_ok
                   and out["kill"]["respawned_to_floor"])
    finally:
        if rc is not None:
            rc.close()
        sup.shutdown()
        try:
            import socket as _s
            s = _s.create_connection(("127.0.0.1", pmux_port),
                                     timeout=2)
            s.sendall(b"exit\n")
            s.close()
        except OSError:
            pass
        pmux.terminate()
        pmux.wait(timeout=30)

    out["zombies_delta"] = zombies() - z0
    out["note"] = ("1-CPU container: all daemons share the host CPU, "
                   "so no wall-clock gates; the gates are counts — "
                   "every client request answered exactly once "
                   "across a SIGKILL and a join, the client-observed "
                   "fleet history checks VALID through the fleet "
                   "itself, the join remapped ~1/N of the shape "
                   "classes, and the migrated session's appends "
                   "stayed O(delta) (no replay) after the "
                   "checkpoint handoff")
    out["gate_ok"] = bool(gate_ok) and out["zombies_delta"] <= 0
    line = json.dumps(out)
    print(line)
    with open(args.out, "w") as fh:
        fh.write(line + "\n")
    if not out["gate_ok"]:
        print("FAIL: elastic gate", file=sys.stderr)
        return 1
    # artifact hygiene: the supervised fleet wrote stores/registrations
    # all over the tree — the static-analysis verdict must stay clean
    # post-run (subprocess so the verdict is independent of this
    # process's jax/import state)
    r = subprocess.run(
        [sys.executable, "-m", "comdb2_tpu.analysis", "--no-trace"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        print("FAIL: static analysis not clean post-run:\n"
              f"{r.stdout}{r.stderr}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
