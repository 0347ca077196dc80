#!/usr/bin/env python3
"""Fuzz the MXU frontier engine — wide-P floor for checker/mxu.

Usage: python scripts/fuzz_mxu.py \
           [n] [--out FUZZ_mxu.json]

Two seeded families, bucketed shapes so runs share compiled programs
(per-seed shapes recompile per seed and can OOM LLVM — CLAUDE.md):

- ``register``: small random register histories (valid + mutated)
  through the MXU engine vs the XLA seg engine AND the host oracle;
  where the fused Pallas kernel serves the shape (P <= 15, K <= 8,
  real TPU) its verdict is cross-checked too — the overlapping-P
  parity floor of the round-10 acceptance.
- ``wide-p-waves``: genuinely concurrent bounded-in-flight wave
  histories (``wide_register_batch_columns``) at P in {16, 24},
  valid + seeded-violation twins, MXU vs the XLA seg engine at a
  frontier that fits both; small free-read counts keep the host
  oracle affordable, so every seed is host-checked as well.

Verdict AND fail-segment parity are asserted (final counts on VALID
only — the cross-engine contract). ``--out`` writes a JSON artifact
with per-family counts so coverage is recorded, not scrollback.
"""
from __future__ import annotations

import json
import random
import sys
from collections import Counter


def _check_all(mm, segs, succ, P, bucket, F=1024):
    """(engine -> (status, fail_seg, n)) for every engine serving the
    bucketed shape."""
    from comdb2_tpu.checker import linear_jax as LJ
    from comdb2_tpu.checker import mxu as MXU
    from comdb2_tpu.checker import pallas_seg as PS

    sizes = dict(n_states=bucket[0], n_transitions=bucket[1])
    out = {}
    st, fa, n = LJ.check_device_seg(
        succ, segs.inv_proc, segs.inv_tr, segs.ok_proc, segs.depth,
        F=F, P=P, **sizes)
    out["xla-seg"] = (int(st), int(fa), int(n))
    if MXU.fits(bucket[0], bucket[1], P):
        st, fa, n = MXU.check_device_mxu(
            succ, segs.inv_proc, segs.inv_tr, segs.ok_proc,
            segs.depth, F=F, P=P, **sizes)
        out["mxu"] = (int(st), int(fa), int(n))
    if PS.available():
        r = PS.check_device_pallas(succ, segs, P=P, **sizes)
        if r is not None:
            out["pallas-fused"] = tuple(int(x) for x in r)
    return out


def _assert_parity(name, seed, verdicts, host_valid, host_index,
                   seg_index):
    base = verdicts["xla-seg"]
    for eng, (st, fa, n) in verdicts.items():
        assert st == base[0], (name, seed, eng, verdicts)
        if st == 0:
            # the kernel's frontier rungs differ from the XLA/MXU
            # capacities — counts only compare at the same capacity,
            # so VALID counts are asserted between the same-F engines
            # (xla/mxu)
            if eng != "pallas-fused":
                assert n == base[2], (name, seed, eng, verdicts)
        else:
            assert fa == base[1], (name, seed, eng, verdicts)
    if host_valid is not None and base[0] != 2:
        assert (base[0] == 0) == host_valid, (name, seed, verdicts)
        if base[0] == 1:
            assert int(seg_index[base[1]]) == host_index, \
                (name, seed, verdicts, host_index)


def main() -> None:
    from comdb2_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    import sys as _sys

    _sys.path.insert(0, "tests")

    from comdb2_tpu.checker import linear_host, linear_jax as LJ
    from comdb2_tpu.models.memo import MemoOverflow, memo as make_memo
    from comdb2_tpu.models.model import cas_register
    from comdb2_tpu.ops import synth_columnar as SC
    from comdb2_tpu.ops.packed import pack_history
    from comdb2_tpu.ops.synth import mutate, register_history

    args = list(sys.argv[1:])
    out_path = None
    if "--out" in args:
        i = args.index("--out")
        if i + 1 >= len(args):
            sys.exit("usage: fuzz_mxu.py [n] [--out FILE]")
        out_path = args[i + 1]
        del args[i:i + 2]
    n = int(args[0]) if args else 60
    c = Counter()

    # --- register family: bucket (64, 8) segs, (32, 64) table ------
    for seed in range(3000, 3000 + n):
        rng = random.Random(seed)
        h = register_history(rng, n_procs=rng.randint(2, 5),
                             n_events=rng.randint(10, 60), values=3,
                             p_info=0.05)
        if rng.random() < 0.5:
            h = mutate(rng, h)
        packed = pack_history(h)
        try:
            mm = make_memo(cas_register(), packed)
        except MemoOverflow:
            c["register", "memo-skip"] += 1
            continue
        if mm.n_states > 32 or mm.n_transitions > 64:
            c["register", "skip"] += 1
            continue
        segs = LJ.make_segments(packed, s_pad=64, k_pad=8)
        segs, p_eff = LJ.remap_slots(segs)
        P = max(p_eff, 1)
        if segs.inv_proc.shape != (64, 8) or P > 15:
            c["register", "skip"] += 1
            continue
        bucket = (32, 64)
        succ = LJ.pad_succ(mm.succ, *bucket)
        verdicts = _check_all(mm, segs, succ, P, bucket, F=128)
        hr = linear_host.check(mm, packed)
        _assert_parity("register", seed, verdicts, hr.valid,
                       hr.op_index, segs.seg_index)
        c["register",
          {0: "ok", 1: "inv", 2: "unk"}[verdicts["xla-seg"][0]]] += 1
        if "pallas-fused" in verdicts:
            c["register", "kernel-crosschecked"] += 1
        if "mxu" not in verdicts:
            c["register", "mxu-nofit"] += 1
    print("register", {k[1]: v for k, v in c.items()
                       if k[0] == "register"}, flush=True)
    checked = sum(c["register", k] for k in ("ok", "inv", "unk"))
    assert checked >= (2 * n) // 3, f"register coverage {checked}/{n}"
    assert c["register", "ok"] and c["register", "inv"]
    assert c["register", "mxu-nofit"] == 0, \
        "every register bucket shape must fit the MXU engine"

    # --- wide-P wave family: P in {16, 24}, valid + violation ------
    for P in (16, 24):
        fam = f"waves-p{P}"
        for seed in range(4000, 4000 + n):
            rng = random.Random(seed)
            n_free = rng.randint(2, 6)       # host-oracle affordable
            n_chain = P - n_free
            n_waves = rng.randint(1, 3)
            violation = rng.random() < 0.5
            cols = SC.wide_register_batch_columns(
                seed, 1, n_waves, n_chain, n_free,
                values=max(16, n_chain + 2), violation=violation)
            packed = SC.pack_register_columns(cols)[0]
            mm = make_memo(cas_register(), packed)
            if mm.n_states > 32 or mm.n_transitions > 64:
                c[fam, "skip"] += 1
                continue
            segs = LJ.make_segments(packed, s_pad=128, k_pad=32)
            segs, p_eff = LJ.remap_slots(segs)
            assert p_eff == P, (p_eff, P)    # genuinely concurrent
            bucket = (32, 64)
            succ = LJ.pad_succ(mm.succ, *bucket)
            verdicts = _check_all(mm, segs, succ, P, bucket, F=1024)
            assert "mxu" in verdicts, "wave shape must fit the engine"
            hr = linear_host.check(mm, packed)
            assert hr.valid is (not violation), (fam, seed, hr.valid)
            _assert_parity(fam, seed, verdicts, hr.valid, hr.op_index,
                           segs.seg_index)
            c[fam,
              {0: "ok", 1: "inv", 2: "unk"}[verdicts["mxu"][0]]] += 1
        print(fam, {k[1]: v for k, v in c.items() if k[0] == fam},
              flush=True)
        assert c[fam, "ok"] and c[fam, "inv"], \
            f"{fam}: both verdict classes must be exercised"
        assert c[fam, "unk"] == 0, \
            f"{fam}: bounded waves must never overflow F=1024"

    if out_path:
        import jax

        families = {}
        for fam in ("register", "waves-p16", "waves-p24"):
            families[fam] = {k[1]: v for k, v in c.items()
                             if k[0] == fam}
            families[fam]["seeds"] = n
        artifact = {
            "seeds_per_family": n,
            "families": families,
            "engines": ["mxu", "xla-seg", "pallas-fused",
                        "linear-host"],
            "backend": jax.default_backend(),
            "verdict": "PASS",   # any mismatch asserts before this
        }
        with open(out_path, "w") as fh:
            json.dump(artifact, fh, indent=1)
        print("artifact written:", out_path, flush=True)


if __name__ == "__main__":
    main()
