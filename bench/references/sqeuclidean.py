"""Plain reference for squared-L2 k-nearest-neighbour search.

Brute force over the whole collection, written from the definition and
importing nothing of the program: the exact top-k of each query, and the
distance of any (query, id) pair. It runs after the window, on whatever
device JAX has, streaming the collection from the host in blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BLOCK = 32768          # collection rows per device block
QBLOCK = 4096          # queries per pass over the collection


@functools.partial(jax.jit, static_argnames=("k",))
def _block_topk(q, qsq, x, offset, best_d, best_i, *, k: int):
    """Merge the k nearest rows of block `x` (rows offset.. of the
    collection; rows of +inf norm are padding) into (best_d, best_i)."""
    xsq = jnp.sum(x * x, axis=1)
    d = (qsq[:, None] + xsq[None, :]
         - 2.0 * jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST))
    ids = offset + jnp.arange(x.shape[0], dtype=jnp.int32)
    cand_d = jnp.concatenate([best_d, d], axis=1)
    cand_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-cand_d, k)
    return -neg, jnp.take_along_axis(cand_i, pos, axis=1)


def topk(queries: np.ndarray, base: np.ndarray, k: int) -> np.ndarray:
    """Ids [Q, k] of each query's k nearest rows of `base`, nearest first,
    at float32 with HIGHEST matmul precision. The collection goes to the
    device once and is scanned there in blocks, QBLOCK queries at a
    time."""
    x = jnp.asarray(base, jnp.float32)
    blocks = [x[lo:lo + BLOCK] for lo in range(0, x.shape[0], BLOCK)]
    if blocks[-1].shape[0] < BLOCK:     # one compiled shape: pad far away
        blocks[-1] = jnp.pad(blocks[-1],
                             ((0, BLOCK - blocks[-1].shape[0]), (0, 0)),
                             constant_values=1e18)
    del x
    out = []
    for qlo in range(0, queries.shape[0], QBLOCK):
        q = jnp.asarray(queries[qlo:qlo + QBLOCK], jnp.float32)
        qsq = jnp.sum(q * q, axis=1)
        best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
        best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
        for j, blk in enumerate(blocks):
            best_d, best_i = _block_topk(q, qsq, blk, jnp.int32(j * BLOCK),
                                         best_d, best_i, k=k)
        out.append(np.asarray(best_i))
    return np.concatenate(out) if out else np.zeros((0, k), np.int64)


def distances(queries: np.ndarray, base: np.ndarray, ids: np.ndarray,
              operands=np.float64) -> np.ndarray:
    """Squared L2 [A, k] between queries[a] and base[ids[a, j]], in float64
    on the host. With operands=bfloat16 the two vectors' products are taken
    from their bfloat16 roundings (the TPU's default matmul precision for
    float32), the norms from the float32 values. Ids outside the
    collection give +inf."""
    n = base.shape[0]
    ok = (ids >= 0) & (ids < n)
    x = base[np.where(ok, ids, 0)].astype(np.float64)          # [A, k, d]
    q = queries.astype(np.float64)[:, None, :]                  # [A, 1, d]
    if operands is np.float64:
        d = np.sum((x - q) ** 2, axis=2)
    else:
        def rounded(a):
            return a.astype(np.float32).astype(operands).astype(np.float64)
        dot = np.sum(rounded(x) * rounded(q), axis=2)
        d = np.sum(x * x, axis=2) + np.sum(q * q, axis=2) - 2.0 * dot
    return np.where(ok, d, np.inf)


BFLOAT16 = ml_dtypes.bfloat16
