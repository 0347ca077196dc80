"""Pallas bitonic sort-pairs kernel tests (run on hardware that Mosaic
supports; skipped on the CPU test mesh)."""

import numpy as np
import pytest

from comdb2_tpu.checker import pallas_sort as PS


requires_pallas = pytest.mark.skipif(
    not PS.sort_pairs_available(),
    reason="Pallas/Mosaic unavailable on this backend")


@requires_pallas
def test_sort_pairs_matches_lexsort():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    B, N = 16, 512
    hi = rng.integers(0, 1 << 20, (B, N)).astype(np.int32)
    lo = rng.integers(0, 1 << 30, (B, N)).astype(np.int32)
    h, l = PS.sort_pairs(jnp.asarray(hi), jnp.asarray(lo))
    h, l = np.asarray(h), np.asarray(l)
    for b in range(B):
        order = np.lexsort((lo[b], hi[b]))
        assert (h[b] == hi[b][order]).all()
        assert (l[b] == lo[b][order]).all()


@requires_pallas
def test_sort_pairs_duplicates_and_sentinels():
    import jax.numpy as jnp

    hi = np.array([[5, 5, 1, 1, 7, 0, 5, 1]], np.int32)
    lo = np.array([[2, 1, 3, 3, 0, 9, 1, 0]], np.int32)
    h, l = PS.sort_pairs(jnp.asarray(hi), jnp.asarray(lo),
                         lanes_per_block=1)
    order = np.lexsort((lo[0], hi[0]))
    assert (np.asarray(h)[0] == hi[0][order]).all()
    assert (np.asarray(l)[0] == lo[0][order]).all()


def test_keys_engine_with_pallas_flag_matches(monkeypatch, request):
    """With the flag forced on, the dedup falls back gracefully when
    Mosaic is unavailable, or produces identical verdicts when it is."""
    import random

    from comdb2_tpu.checker import linear_jax as LJ
    from comdb2_tpu.checker import linear_host
    from comdb2_tpu.checker.batch import pack_batch, check_batch
    from comdb2_tpu.models import model as M
    from comdb2_tpu.models.memo import memo as make_memo
    from comdb2_tpu.ops.packed import pack_history
    from tests import histgen

    if not PS.sort_pairs_available():
        pytest.skip("Pallas unavailable; engine uses the XLA sort")
    monkeypatch.setattr(LJ, "_USE_PALLAS_SORT", True)
    # flag isn't part of the jit static key: drop cached executables so
    # the Pallas path really traces, and drop them again afterwards so
    # flag-off callers don't reuse the Pallas-compiled executable
    LJ.check_device_keys.clear_cache()
    request.addfinalizer(LJ.check_device_keys.clear_cache)
    model = M.cas_register()
    hs, want = [], []
    for seed in range(8):
        rng = random.Random(800 + seed)
        h = histgen.register_history(rng, n_procs=3,
                                     n_events=rng.randint(6, 20))
        if seed % 2:
            h = histgen.mutate(rng, h)
        hs.append(h)
        p = pack_history(h)
        want.append(linear_host.check(make_memo(model, p), p).valid)
    batch = pack_batch(hs, model)
    info = {}
    st, _, _ = check_batch(batch, F=128, info=info)
    assert info["engine"] == "keys"
    got = [bool(s == 0) if s != 2 else "unknown" for s in st]
    assert got == want
