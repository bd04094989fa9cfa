"""Exact (flat) k-NN search — ground truth for recall measurement and the
training-data generator, plus the sharded brute-force baseline.

Single-device path chunks over the DB; the distributed path shards the DB
rows across the mesh and merges per-shard top-k with one small all-gather
(see dist/collectives.py) — collective volume O(B*k*devices), independent
of N.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.padding import PAD_DIST, pad_dists, pad_ids


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def search(q: jax.Array, x: jax.Array, k: int,
           chunk: int = 65536) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k. q: [B, D], x: [N, D] -> (dist [B,k] ascending, idx [B,k]).

    The q.x contraction runs at HIGHEST precision: this is the ground
    truth recall is measured against, and a TPU's default f32 matmul is a
    single bf16 pass."""
    n, d = x.shape
    b = q.shape[0]
    qf = q.astype(jnp.float32)
    qsq = jnp.sum(qf**2, axis=1, keepdims=True)
    n_chunks = max(1, -(-n // chunk))
    pad = n_chunks * chunk - n
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xsq = jnp.concatenate([jnp.sum(xp[:n].astype(jnp.float32) ** 2, axis=1),
                           pad_dists((pad,))])
    xc = xp.reshape(n_chunks, chunk, d)
    xsqc = xsq.reshape(n_chunks, chunk)

    def body(carry, inp):
        best_d, best_i = carry
        xi, xsqi, off = inp
        dist = xsqi[None, :] - 2.0 * jnp.matmul(
            qf, xi.astype(jnp.float32).T,
            precision=jax.lax.Precision.HIGHEST)
        cand_d = jnp.concatenate([best_d, dist], axis=1)
        neg, pos = jax.lax.top_k(-cand_d, k)
        # Map winning positions to ids after the top-k: a [B, k + chunk]
        # id array would be loop-invariant and constant-folded into the
        # program (hundreds of MB at B=2000).
        kept = jnp.take_along_axis(best_i, jnp.minimum(pos, k - 1), axis=1)
        return (-neg, jnp.where(pos < k, kept, off + pos - k)), None

    init = (pad_dists((b, k)), pad_ids((b, k)))
    offs = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)
    (best_d, best_i), _ = jax.lax.scan(body, init, (xc, xsqc, offs))
    best_d = jnp.where(best_i >= 0, jnp.maximum(best_d + qsq, 0.0), PAD_DIST)
    return best_d, best_i


def search_sharded(q: jax.Array, x: jax.Array, k: int, mesh
                   ) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k with the database row-sharded over mesh axis "model"
    (dist/collectives.py); numerically matches `search`."""
    from repro.dist import collectives  # local import: dist uses kernels
    return collectives.sharded_flat_search(q, x, k, mesh)


def recall_at_k(found_ids: jax.Array, true_ids: jax.Array) -> jax.Array:
    """recall@k: |found ∩ true| / k. found/true: int32[B, k] (-1 = empty)."""
    matches = (found_ids[:, :, None] == true_ids[:, None, :]) & (found_ids[:, :, None] >= 0)
    return matches.any(axis=2).sum(axis=1).astype(jnp.float32) / true_ids.shape[1]
