"""Batched GBDT inference in pure JAX.

`predict` is the reference: a per-tree gather descent, kept for tests.
`predict_efficient` is the XLA path every caller uses, the served recall
predictor included: a gather-free descent of element-wise selects and
reductions, which XLA fuses on the TPU's vector unit, where a dynamic
gather lowers to near-serial element fetches. kernels/gbdt_predict.py is
the Pallas VMEM-resident version, validated against both."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.gbdt.model import GBDTParams


def predict(params: GBDTParams, x: jax.Array) -> jax.Array:
    """Predict for a batch.

    Args:
      params: ensemble.
      x: float32[B, F] raw features.
    Returns:
      float32[B] predictions.
    """
    depth = params.depth
    num_trees = params.num_trees
    b = x.shape[0]

    # node[b, t]: current node index per (query, tree); predicated descent.
    node = jnp.zeros((b, num_trees), jnp.int32)
    for _ in range(depth):
        f = jnp.take_along_axis(params.feat[None, :, :].repeat(b, 0), node[:, :, None], axis=2)[..., 0]
        t = jnp.take_along_axis(params.thresh[None, :, :].repeat(b, 0), node[:, :, None], axis=2)[..., 0]
        xv = jnp.take_along_axis(x, jnp.maximum(f, 0), axis=1)  # [B, T]
        go_right = (xv > t) & (f >= 0)
        node = 2 * node + 1 + go_right.astype(jnp.int32)
    leaf_idx = node - (2**depth - 1)
    leaf_val = jnp.take_along_axis(params.leaf[None, :, :].repeat(b, 0), leaf_idx[:, :, None], axis=2)[..., 0]
    return params.base + leaf_val.sum(axis=1)


# Rows per pass of the descent. A backend that does not fuse a select into
# its reduction (XLA's CPU backend) holds one [2^depth, rows, T] select per
# pass: ~52 MB at depth 6 and 100 trees. The served batch is one pass.
_ROWS = 2048


@jax.jit
def predict_efficient(params: GBDTParams, x: jax.Array) -> jax.Array:
    """Same function as `predict`, with no gather.

    The node position is level-local: at level d each tree sits at one of
    the level's 2^d nodes. Its feature and threshold are picked by
    compare-and-select over those 2^d nodes, the feature's value over the
    F columns, and at the end the leaf value over the 2^depth leaves.
    Every pick is exact: `jnp.where`, then a max or a sum over fills that
    cannot change the kept entry (-1 below every feature, -inf below every
    threshold, 0 beside the one value kept). Never a multiply, since
    degenerate nodes hold `thresh = inf` and 0 * inf is NaN; never a
    matmul, which rounds its operands to bfloat16 at the TPU's default
    precision. Intermediates are [nodes, B, T], so the tree axis is minor
    (lanes) and each reduction runs across vector registers. A batch of
    more than `_ROWS` rows is descended `_ROWS` at a time; jitted, so an
    eager caller (the fit's holdout) gets the same fusion.
    """
    b, num_feat = x.shape
    if b <= _ROWS:
        return params.base + _descend(params, x)
    n = -(-b // _ROWS)
    xs = jnp.pad(x, ((0, n * _ROWS - b), (0, 0))).reshape(n, _ROWS, num_feat)
    out = jax.lax.map(lambda xr: _descend(params, xr), xs)
    return params.base + out.reshape(-1)[:b]


def _descend(params: GBDTParams, x: jax.Array) -> jax.Array:
    """Sum over trees of the leaf each row of x reaches (no base)."""
    b, num_feat = x.shape
    xt = x.T[:, :, None]                                      # [F, B, 1]
    col = jnp.arange(num_feat, dtype=jnp.int32)[:, None, None]

    node = jnp.zeros((b, params.num_trees), jnp.int32)        # [B, T]
    for d in range(params.depth):
        lo, width = 2**d - 1, 2**d
        at = jnp.arange(width, dtype=jnp.int32)[:, None, None] == node
        f = jnp.where(at, params.feat[:, lo:lo + width].T[:, None], -1).max(0)
        t = jnp.where(at, params.thresh[:, lo:lo + width].T[:, None],
                      -jnp.inf).max(0)
        xv = jnp.where(col == f, xt, 0.0).sum(0)              # [B, T]
        go_right = (xv > t) & (f >= 0)
        node = 2 * node + go_right.astype(jnp.int32)
    at = jnp.arange(2**params.depth, dtype=jnp.int32)[:, None, None] == node
    leaf_val = jnp.where(at, params.leaf.T[:, None], 0.0).sum(0)
    return leaf_val.sum(axis=1)
