"""Pallas TPU kernel for GBDT ensemble inference (DARTH's recall predictor).

The whole ensemble lives in VMEM (100 trees x 63 internal nodes x
(feat,thr) + 64 leaves ~= 75 KB), the batch is tiled over the grid, and the
root-to-leaf descent is *gather-free*: node positions are resolved with
level-local one-hot contractions (level d has only 2^d nodes, so the
one-hot work is tiny at the top and bounded by the leaf level).

Why a kernel at all: the paper's constraint (§3.2) is that predictor
invocation cost must not cancel early-termination savings. Keeping the
ensemble VMEM-resident and fusing the descent means one invocation for a
whole active batch costs less than a single IVF bucket probe.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode


def _gbdt_kernel(x_ref, feat_ref, thr_ref, leaf_ref, out_ref, *,
                 depth: int, num_feat: int):
    x = x_ref[...].astype(jnp.float32)       # [bq, F]
    feat = feat_ref[...]                     # [T, NI] int32
    thr = thr_ref[...]                       # [T, NI] f32
    leaf = leaf_ref[...]                     # [T, NL] f32
    bq = x.shape[0]
    t = feat.shape[0]

    node = jnp.zeros((bq, t), jnp.int32)     # level-local position
    for d in range(depth):
        lo = 2**d - 1
        width = 2**d
        feat_d = jax.lax.slice(feat, (0, lo), (t, lo + width))   # [T, w]
        thr_d = jax.lax.slice(thr, (0, lo), (t, lo + width))
        pos = jax.lax.broadcasted_iota(jnp.int32, (bq, t, width), 2)
        oh = (pos == node[:, :, None]).astype(jnp.float32)       # [bq,T,w]
        f_sel = jnp.sum(oh * feat_d[None].astype(jnp.float32), axis=2)
        t_sel = jnp.sum(oh * thr_d[None], axis=2)                # [bq,T]
        fcol = jax.lax.broadcasted_iota(jnp.int32, (bq, t, num_feat), 2)
        ohf = (fcol == jnp.maximum(f_sel, 0.0).astype(jnp.int32)[:, :, None])
        xv = jnp.sum(jnp.where(ohf, x[:, None, :], 0.0), axis=2)  # [bq,T]
        go_right = (xv > t_sel) & (f_sel >= 0.0)
        node = 2 * node + go_right.astype(jnp.int32)

    n_leaf = 2**depth
    pos = jax.lax.broadcasted_iota(jnp.int32, (bq, t, n_leaf), 2)
    oh = (pos == node[:, :, None]).astype(jnp.float32)
    vals = jnp.sum(oh * leaf[None], axis=2)                      # [bq, T]
    out_ref[...] = jnp.sum(vals, axis=1, keepdims=True)          # [bq, 1]


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gbdt_predict_padded(x: jax.Array, feat: jax.Array, thr: jax.Array,
                        leaf: jax.Array, *, bq: int = 64,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Pre-padded kernel entry. x: [B, F], B % bq == 0. Returns [B] (no base)."""
    b, num_feat = x.shape
    assert b % bq == 0, (b, bq)
    t, n_internal = feat.shape
    depth = (n_internal + 1).bit_length() - 1
    kernel = functools.partial(_gbdt_kernel, depth=depth, num_feat=num_feat)
    out = pl.pallas_call(
        kernel,
        grid=(b // bq,),
        in_specs=[
            pl.BlockSpec((bq, num_feat), lambda i: (i, 0)),
            pl.BlockSpec(feat.shape, lambda i: (0, 0)),
            pl.BlockSpec(thr.shape, lambda i: (0, 0)),
            pl.BlockSpec(leaf.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(interpret),
    )(x, feat, thr, leaf)
    return out[:, 0]
