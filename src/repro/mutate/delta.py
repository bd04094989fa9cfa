"""Delta tier: a fixed-capacity ring of recently inserted vectors.

The streaming-update subsystem keeps the base index (IVF bucket store /
HNSW graph) immutable between compactions; inserts land here, in a flat
[capacity, D] buffer that every search scans brute-force with the fused
`l2_topk` kernel and merges into the base top-k (LSM memtable, vector
edition). Slots follow the repo-wide padding contract so an empty or
tombstoned slot can never surface in a result set:

    vecs 0, ids -1, sqnorm +inf

(the same convention dist.place_index uses for shard padding). The ring
is replicated on every shard when the base index is mesh-placed — it is
small by construction, and replicating it keeps the delta scan free of
collectives.

Ring-cursor bookkeeping lives on the host (mutate.index.MutableIndex):
the device arrays carry no cursor, so the same DeltaTier pytree crosses
every jit boundary with a stable treedef and inserts never retrace the
serving chunks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.padding import (PAD_ID, pad_dists, pad_id_scalar, pad_ids,
                                pad_sqnorm_scalar)
from repro.kernels import ops


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeltaTier:
    vecs: jax.Array    # f32[capacity, D] (zeros when empty)
    ids: jax.Array     # i32[capacity] global ids (-1 = empty/tombstoned)
    sqnorm: jax.Array  # f32[capacity] (+inf = empty/tombstoned)

    @property
    def capacity(self) -> int:
        return self.ids.shape[0]

    @property
    def dim(self) -> int:
        return self.vecs.shape[1]


def make_delta(capacity: int, dim: int) -> DeltaTier:
    """Empty delta ring (all slots carry the pad convention)."""
    return DeltaTier(
        vecs=jnp.zeros((capacity, dim), jnp.float32),
        ids=pad_ids((capacity,)),
        sqnorm=pad_dists((capacity,)),
    )


@jax.jit
def write(delta: DeltaTier, slots: jax.Array, vecs: jax.Array,
          ids: jax.Array) -> DeltaTier:
    """Scatter `vecs`/`ids` into ring `slots`. Padded entries (slot -1)
    are routed out of bounds, which JAX scatters drop — so the host can
    pad every write to one fixed length and never retrace."""
    s = jnp.where(slots >= 0, slots, delta.ids.shape[0])
    sq = jnp.sum(vecs.astype(jnp.float32) ** 2, axis=1)
    return DeltaTier(
        vecs=delta.vecs.at[s].set(vecs.astype(jnp.float32)),
        ids=delta.ids.at[s].set(ids.astype(jnp.int32)),
        sqnorm=delta.sqnorm.at[s].set(sq),
    )


@jax.jit
def tombstone(delta: DeltaTier, slots: jax.Array) -> DeltaTier:
    """Mask ring `slots` back to the pad convention (ids -1, sqnorm +inf)
    so a deleted insert can never re-enter a top-k. Slot -1 = no-op."""
    s = jnp.where(slots >= 0, slots, delta.ids.shape[0])
    return dataclasses.replace(
        delta,
        ids=delta.ids.at[s].set(pad_id_scalar(delta.ids.dtype)),
        sqnorm=delta.sqnorm.at[s].set(pad_sqnorm_scalar(delta.sqnorm.dtype)),
    )


@jax.jit
def live_count(delta: DeltaTier) -> jax.Array:
    return jnp.sum(delta.ids >= 0).astype(jnp.int32)


def delta_topk(delta: DeltaTier, q: jax.Array, k: int, *,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Brute-force scan of the delta tier with the fused l2_topk kernel.

    Returns (dist f32[B, k] squared ascending, global ids i32[B, k],
    live i32[] scanned-slot count, ninserts i32[B] finite candidates).
    Empty / tombstoned slots enter with sqnorm +inf so they can never
    win; their ids are masked to -1 on the way out.
    """
    d, i_loc = ops.l2_topk(q, delta.vecs, k=k, x_sqnorm=delta.sqnorm,
                           interpret=interpret)
    g = delta.ids[jnp.maximum(i_loc, 0)]
    g = jnp.where((i_loc >= 0) & jnp.isfinite(d), g, PAD_ID)
    nins = jnp.sum(jnp.isfinite(d), axis=1).astype(jnp.int32)
    return d, g, live_count(delta), nins
