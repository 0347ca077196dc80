"""The main path's kernels compile for a described TPU v5e.

Nothing runs here: each test lowers and compiles for a chip that is
described, not attached (``jax.experimental.topologies``), so what
Mosaic or the TPU compiler would refuse is red at tier 1 without chip
time. The topology is described inside a module fixture, never at
import: only one process may load the TPU library, and pytest-xdist
workers import every test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from comdb2_tpu.checker import pallas_seg as PS
from comdb2_tpu.checker import pallas_sort as PSORT

I32 = jnp.int32

# (n_states, n_transitions, P, K) -> (n_words, rows, chunk): the three
# kernel tiers — p5 one word, p10 two words on 16 rows, p15 three
TIERS = {
    "p5": ((8, 6, 5, 4), (1, 8, 1024)),
    "p10": ((8, 14, 10, 8), (2, 16, 768)),
    "p15": ((64, 30, 15, 8), (3, 16, 768)),
}


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure = no chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but can't be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(tier):
    (ns, nt, P, K), (n_words, rows, chunk) = TIERS[tier]
    spec = PS.spec_for(ns, nt, P, K)
    assert (spec.n_words, spec.rows, spec.chunk) == (n_words, rows,
                                                     chunk), spec
    return spec


def _scan_args(spec, b_pad, sharding, lead=()):
    """ShapeDtypeStructs for ``_scan_fn``'s (seg_chunks, ws, stat,
    res, table, stride); ``lead`` prepends a shard axis to all but the
    replicated table and stride."""
    seg_w = 2 + 2 * spec.K
    sh, rep = sharding

    def s(shape, shd=sh):
        return jax.ShapeDtypeStruct(shape, I32, sharding=shd)

    return (s(lead + (2, spec.chunk, seg_w)),
            tuple(s(lead + (PS.carry_rows(spec), PS.LANES))
                  for _ in range(spec.n_words)),
            s(lead + (1, PS.LANES)), s(lead + (b_pad, PS.LANES)),
            s((spec.table_rows_pad, PS.LANES), rep), s((), rep))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("stream", [False, True],
                         ids=["single", "stream"])
@pytest.mark.parametrize("tier", sorted(TIERS))
def test_fused_scan_compiles_for_v5e(one_chip, tier, stream):
    spec = _spec(tier)
    run = PS._scan_fn(spec, 8, stream)
    compiled = run.lower(*_scan_args(spec, 8,
                                     (one_chip, one_chip))).compile()
    assert _has_kernel(compiled)


def test_pallas_sort_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((256, 4096), I32, sharding=one_chip)
    compiled = jax.jit(PSORT.sort_pairs).lower(x, x).compile()
    assert _has_kernel(compiled)


def test_sharded_stream_dispatch_compiles_for_v5e_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("batch",))
    spec = _spec("p5")
    shard = NamedSharding(mesh, PartitionSpec("batch"))
    rep = NamedSharding(mesh, PartitionSpec())
    run = PS._sharded_scan_fn(spec, 64, mesh, "batch")
    compiled = run.lower(*_scan_args(spec, 64, (shard, rep),
                                     lead=(4,))).compile()
    assert _has_kernel(compiled)
    # pure data parallelism: the shards never talk to each other
    text = compiled.as_text()
    for coll in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute"):
        assert coll not in text, coll
    mem = compiled.memory_analysis()
    assert mem is not None
