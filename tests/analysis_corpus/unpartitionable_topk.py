"""Known-bad corpus: the unpartitionable-TopK bug class (PR 6).

A top-k merge runs OUTSIDE the shard_map over candidates whose slot
(batch) dim is split across host groups — the pre-`pin_merge` layout
of the sharded engine steps. GSPMD cannot partition the TopK/sort
custom-call over the sharded dim, so it materialises the operand with
an `all-gather` over dim 0 right in front of the merge: every chunk
step pays a cross-host gather of the whole candidate array. The
gate's unpartitionable-topk pass must flag the sort/TopK with a
file:line into this module (python -m repro.analysis --selftest
asserts it does; needs a forced multidevice CPU).
"""
MIN_DEVICES = 2
EXPECT_PASS = "unpartitionable-topk"


def build_bad():
    """The bad program: (jitted_fn, args) ready to lower."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((jax.device_count(),), ("hosts",))
    cand = jax.device_put(
        jnp.asarray(np.random.default_rng(0).normal(
            size=(8 * jax.device_count(), 128)).astype(np.float32)),
        NamedSharding(mesh, P("hosts", None)))

    @jax.jit
    def merge(c):
        # BUG: the candidate rows are hosts-split, but this top-k runs
        # outside any shard_map — GSPMD all-gathers dim 0 to feed it.
        return jax.lax.top_k(c, 8)

    return merge, (cand,)
