"""Seeded violation: production code routing mesh traffic onto the
vmap-sharded TEST ORACLE. ``linear_jax.check_sharded`` shard_maps the
vmap engine, which lowers ~20x worse per lane than the key-packed
encoding — round 7 removed the last production route; serving
traffic goes through ``checker.batch.check_batch``'s stream/keys
sharded engines."""


def serve_batch(mesh, succ, batch):
    from comdb2_tpu.checker.linear_jax import check_sharded

    # BUG: the oracle on the serving path
    return check_sharded(mesh, succ, batch.kind, batch.proc,
                         batch.tr, F=256, P=4)
