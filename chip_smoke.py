#!/usr/bin/env python3
"""On-chip smoke of the main path: history -> pack -> segment -> fused
Pallas frontier search -> verdict, through the entry points users call.

One process, the only one that touches JAX (the chip belongs to one
process at a time; the served phase runs the daemon on a thread).
There is no CPU branch: without a TPU it exits non-zero before any
phase. All data comes from ``--seed``.

Phases (one JSON line each; wall time, XLA lowerings, Mosaic builds,
the lowerings outside the declared compile surface (PROGRAMS.md) and
peak device bytes are informational, not metrics):

- ``device``: the TPU the run holds.
- ``north-star``: the 50k-op cas-register history of BASELINE.json
  configs[3] (``ops.synth.register_history``, two events per op)
  through ``checker.linear.analysis`` at concurrency 5 and 10; each must come
  back valid from the fused kernel, and its seeded corrupted twin (one
  read of a never-written value) invalid.
- ``batch``: 256 histories x 800 events through
  ``check_batch`` on the stream kernel, every 8th a
  corrupted twin; verdicts must match construction everywhere and the
  host oracle ``linear_host.check`` on a seeded sample.
- ``served``: a ``VerifierDaemon`` on a thread, ``ServiceClient`` over
  loopback: linear check + twin, ``kind:"wl"`` bank + its
  ``violation="total"`` twin, ``kind:"txn"`` G2-item fixture; every
  reply must match the offline answer.

``--multichip`` runs only the four-chip path: a 1024-history batch
sharded over ``service.sharding.make_mesh(4)`` against the same batch
on one device; verdicts must be bit-identical and every device must
have run live histories.

The last stdout line is ``{"ok": true, "device": {...}}`` and appears
only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

N_OPS = 50_000                 # BASELINE.json configs[3]
N_EVENTS = 2 * N_OPS           # an invoke and a completion per op
B_HISTS, B_EVENTS = 256, 800   # 256 histories x 400 ops
MULTI_HISTS = 1024
MULTI_DEVICES = 4
TWIN_EVERY = 8                 # batch lanes i % 8 == 3 are corrupted
ORACLE_SAMPLE = (12, 8)        # (valid, corrupted) histories re-checked
NEVER_WRITTEN = 1 << 20        # register values are < 5


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(AssertionError):
    pass


def expect(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Phase:
    """Times one phase and counts what it compiled; ``line`` collects
    the phase's own fields and is printed on exit."""

    def __init__(self, name: str, devices):
        self.name = name
        self.devices = devices
        self.line: dict = {"phase": name}

    def __enter__(self) -> "Phase":
        from comdb2_tpu.utils import compile_guard

        self._guard = compile_guard.CompileGuard().start()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        wall = time.perf_counter() - self._t0
        self._guard.stop()
        c = self._guard.counters()
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            peaks.append(stats.get("peak_bytes_in_use"))
        self.line.update(
            wall_s=wall, xla_lowerings=c["xla_lowerings"],
            mosaic_builds=c["mosaic_builds"],
            compile_offenders=[r.format()
                               for r in self._guard.offenders()],
            peak_bytes_in_use=peaks if len(peaks) > 1 else peaks[0])
        if exc[0] is not None:
            self.line["error"] = f"{exc[0].__name__}: {exc[1]}"
        emit(self.line)


def corrupt(history, rng: random.Random):
    """The twin: one ok read now returns a value no op ever wrote."""
    from comdb2_tpu.ops import op as O

    reads = [i for i, op in enumerate(history)
             if op.type == "ok" and op.f == "read"]
    i = rng.choice(reads)
    twin = list(history)
    twin[i] = O.ok(history[i].process, "read", NEVER_WRITTEN)
    return twin


def register_batch(seed: int, n: int):
    """The batch phase's histories, every TWIN_EVERY-th corrupted;
    returns (histories, corrupted lane mask)."""
    from comdb2_tpu.ops.synth import register_history

    rng = random.Random(seed)
    hs, bad = [], []
    for i in range(n):
        h = register_history(rng, n_procs=5, n_events=B_EVENTS,
                             values=5, p_info=0.0)
        twin = i % TWIN_EVERY == 3
        hs.append(corrupt(h, rng) if twin else h)
        bad.append(twin)
    return hs, bad


def phase_north_star(seed: int, n_events: int, devices) -> None:
    from comdb2_tpu.checker.linear import analysis
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.synth import register_history

    # (procs, max_pending): concurrency 5, and 10 with at most 5 open
    for k, (procs, max_pending) in enumerate(((5, None), (10, 5))):
        with Phase("north-star", devices) as ph:
            rng = random.Random(seed + 100 + k)
            h = register_history(rng, n_procs=procs, n_events=n_events,
                                 values=5, p_info=0.0,
                                 max_pending=max_pending)
            twin = corrupt(h, rng)
            a = analysis(cas_register(), h)
            b = analysis(cas_register(), twin)
            ph.line.update(
                procs=procs, events=len(h),
                ops=sum(op.type == "invoke" for op in h),
                valid=a.valid, engine=a.info.get("engine"),
                effective_slots=a.info.get("effective_slots"),
                twin_valid=b.valid, twin_engine=b.info.get("engine"),
                twin_op_index=b.op_index)
            expect(a.valid is True, f"p{procs} history not valid")
            expect(b.valid is False, f"p{procs} twin not invalid")
            expect(a.info.get("engine") == "pallas-fused"
                   and b.info.get("engine") == "pallas-fused",
                   "fused kernel did not serve the north-star check")


def phase_batch(seed: int, n: int, devices) -> None:
    import numpy as np

    from comdb2_tpu.checker import linear_host
    from comdb2_tpu.checker import linear_jax as LJ
    from comdb2_tpu.checker.batch import check_batch, pack_batch
    from comdb2_tpu.models.memo import memo as make_memo
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.packed import pack_history

    with Phase("batch", devices) as ph:
        hs, bad = register_batch(seed + 200, n)
        batch = pack_batch(hs, cas_register())
        info: dict = {}
        status, _, _ = check_batch(batch, info=info)
        status = np.asarray(status)
        want = np.where(bad, LJ.INVALID, LJ.VALID)
        rng = random.Random(seed + 201)
        idx_ok = [i for i in range(n) if not bad[i]]
        idx_bad = [i for i in range(n) if bad[i]]
        sample = sorted(rng.sample(idx_ok, ORACLE_SAMPLE[0])
                        + rng.sample(idx_bad, ORACLE_SAMPLE[1]))
        agree = 0
        for i in sample:
            packed = pack_history(hs[i])
            r = linear_host.check(make_memo(cas_register(), packed),
                                  packed)
            agree += int(status[i]) == (LJ.VALID if r.valid
                                        else LJ.INVALID)
        ph.line.update(
            histories=n, events=sum(len(h) for h in hs),
            engine=info.get("engine"),
            escalated=info.get("escalated"),
            valid=int((status == LJ.VALID).sum()),
            invalid=int((status == LJ.INVALID).sum()),
            matches_construction=bool((status == want).all()),
            oracle_sample=len(sample), oracle_agree=agree)
        expect(info.get("engine") == "stream",
               f"batch engine {info.get('engine')!r}, want stream")
        expect((status == want).all(), "batch verdicts wrong")
        expect(agree == len(sample), "host oracle disagrees")


def phase_served(seed: int, devices) -> None:
    import threading

    from comdb2_tpu.checker.checkers import check_safe
    from comdb2_tpu.checker.linear import analysis
    from comdb2_tpu.checker.wl.synth import bank_batch
    from comdb2_tpu.checker.workloads import bank_checker
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops.history import parse_history
    from comdb2_tpu.ops.synth import register_history
    from comdb2_tpu.service.client import ServiceClient
    from comdb2_tpu.service.core import VerifierCore
    from comdb2_tpu.service.daemon import VerifierDaemon
    from comdb2_tpu.txn import check_txn

    with Phase("served", devices) as ph:
        rng = random.Random(seed + 300)
        lin = register_history(rng, n_procs=5, n_events=B_EVENTS,
                               values=5, p_info=0.0)
        lin_twin = corrupt(lin, rng)
        bank, model = bank_batch(seed + 301, 1)
        bank_twin, _ = bank_batch(seed + 301, 1, violation="total")
        with open(os.path.join(HERE, "tests", "fixtures", "txn",
                               "g2_item.edn")) as fh:
            g2 = fh.read()
        # offline answers first: the daemon thread owns the device
        # while it serves
        offline = {
            "linear": analysis(cas_register(), lin).valid,
            "linear-twin": analysis(cas_register(), lin_twin).valid,
            "bank": check_safe(bank_checker, {}, model,
                               bank[0])["valid?"],
            "bank-twin": check_safe(bank_checker, {}, model,
                                    bank_twin[0])["valid?"],
        }
        txn_off = check_txn(parse_history(g2), backend="host")
        offline["txn-g2"] = txn_off["valid?"]
        expect(offline == {"linear": True, "linear-twin": False,
                           "bank": True, "bank-twin": False,
                           "txn-g2": False},
               f"offline answers off construction: {offline}")

        daemon = VerifierDaemon(VerifierCore(), port=0)
        th = threading.Thread(target=daemon.run, name="daemon",
                              daemon=True)
        th.start()
        replies = {}
        try:
            with ServiceClient(port=daemon.port,
                               timeout_s=600.0) as c:
                replies["linear"] = c.check(lin)
                replies["linear-twin"] = c.check(lin_twin)
                replies["bank"] = c.check_wl(bank[0], "bank", wl=model)
                replies["bank-twin"] = c.check_wl(bank_twin[0], "bank",
                                                  wl=model)
                replies["txn-g2"] = c.check(g2, txn=True)
                c.shutdown()
        finally:
            daemon.stop()
            th.join(timeout=60)
        ph.line["replies"] = {
            k: {f: r.get(f) for f in ("valid", "engine",
                                      "anomaly_class", "latency_ms")
                if f in r}
            for k, r in replies.items()}
        for k, want in offline.items():
            expect(replies[k].get("valid") == want,
                   f"served {k}: {replies[k].get('valid')!r} != "
                   f"offline {want!r}")
        expect(replies["txn-g2"].get("anomaly_class") == "G2-item"
               and txn_off["counterexample"]["class"] == "G2-item",
               "served txn reply does not name the G2-item cycle")
        expect(not th.is_alive(), "daemon thread did not stop")


def phase_multichip(seed: int, devices) -> None:
    import numpy as np

    from comdb2_tpu.checker import linear_jax as LJ
    from comdb2_tpu.checker.batch import check_batch, pack_batch
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.service.sharding import make_mesh

    with Phase("multichip", devices) as ph:
        hs, bad = register_batch(seed + 400, MULTI_HISTS)
        mesh = make_mesh(MULTI_DEVICES)
        info_m: dict = {}
        got_m = check_batch(pack_batch(hs, cas_register()), mesh=mesh,
                            info=info_m)
        info_1: dict = {}
        got_1 = check_batch(pack_batch(hs, cas_register()),
                            info=info_1)
        same = all(np.array_equal(a, b) for a, b in zip(got_m, got_1))
        per_dev = info_m.get("shard_histories", {})
        want = np.where(bad, LJ.INVALID, LJ.VALID)
        ph.line.update(
            histories=MULTI_HISTS, engine_mesh=info_m.get("engine"),
            engine_one=info_1.get("engine"),
            bit_identical=same,
            matches_construction=bool((got_1[0] == want).all()),
            histories_per_device=per_dev)
        expect(info_m.get("engine") == "stream-sharded"
               and info_1.get("engine") == "stream",
               "multichip engines off the stream kernel")
        expect(same, "mesh verdicts differ from one device")
        expect((got_1[0] == want).all(), "multichip verdicts wrong")
        expect(len(per_dev) == MULTI_DEVICES
               and all(v > 0 for v in per_dev.values()),
               f"not every device ran histories: {per_dev}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--multichip", action="store_true",
                    help=f"run only the {MULTI_DEVICES}-chip sharded "
                         "batch against one device")
    args = ap.parse_args(argv)

    import comdb2_tpu  # noqa: F401 — fails here outside a checkout
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    from comdb2_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    used = devices[:MULTI_DEVICES] if args.multichip else devices[:1]
    if len(used) < (MULTI_DEVICES if args.multichip else 1):
        print(f"chip_smoke: --multichip needs {MULTI_DEVICES} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    emit({"phase": "device", "platform": devices[0].platform,
          "kind": devices[0].device_kind, "visible": len(devices),
          "used": len(used), "jax": jax.__version__,
          "compile_cache": cache})
    try:
        if args.multichip:
            phase_multichip(args.seed, used)
        else:
            phase_north_star(args.seed, N_EVENTS, used)
            phase_batch(args.seed, B_HISTS, used)
            phase_served(args.seed, used)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(used)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
