"""Fused squared-L2 distance + running top-k Pallas TPU kernel.

This is DARTH's compute hot spot: >95% of search FLOPs are q·X^T distance
tiles (IVF probes, HNSW beam expansions, flat ground-truth scans).

Design (TPU-native, see DESIGN.md §6):
  * ranking identity ||q-x||^2 = ||q||^2 + ||x||^2 - 2 q.x — the ||q||^2
    term is rank-invariant, added back by the wrapper;
  * grid (query tiles [parallel], db tiles [arbitrary/sequential]); the db
    axis walks sequentially and accumulates a running per-row top-k in the
    *output* block (revisited across the db axis), so the B×N distance
    matrix never exists in HBM;
  * the MXU does `q_tile @ x_tile.T` (f32 accumulate); the top-k merge is a
    K-step masked-min extraction over [bq, K + bn] — O(K·(K+bn)) VPU work
    per tile, amortized against 2·D·bn MXU flops per row;
  * BlockSpecs keep q (bq×D), x (bn×D), running top-k (bq×K) in VMEM:
    128·1024·4 + 512·1024·4 + small ≈ 2.6 MB at D=1024.

Padding contract (enforced by ops.l2_topk): B % bq == 0, N % bn == 0,
padded db rows carry x_sqnorm=+inf so they never enter the top-k.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode

_NEG_INF = float("-inf")


def _l2_topk_kernel(q_ref, x_ref, xsq_ref, outd_ref, outi_ref, *, k: int,
                    bn: int, precision):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        outd_ref[...] = jnp.full_like(outd_ref, jnp.inf)
        outi_ref[...] = jnp.full_like(outi_ref, -1)

    q = q_ref[...].astype(jnp.float32)            # [bq, D]
    x = x_ref[...].astype(jnp.float32)            # [bn, D]
    xsq = xsq_ref[...].astype(jnp.float32)        # [1, bn]

    # MXU: [bq, bn] partial distances (missing rank-invariant ||q||^2).
    dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)
    tile_d = xsq - 2.0 * dots                     # [bq, bn]
    base = j * bn
    tile_i = base + jax.lax.broadcasted_iota(jnp.int32, tile_d.shape, 1)

    run_d = outd_ref[...]                         # [bq, k]
    run_i = outi_ref[...]

    # Merge: K-step masked-min extraction over the concatenated candidates.
    cand_d = jnp.concatenate([run_d, tile_d], axis=1)     # [bq, k+bn]
    cand_i = jnp.concatenate([run_i, tile_i], axis=1)
    width = cand_d.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)
    new_d = jnp.zeros_like(run_d)
    new_i = jnp.zeros_like(run_i)
    out_col = jax.lax.broadcasted_iota(jnp.int32, run_d.shape, 1)

    def body(t, carry):
        cand_d, cand_i, new_d, new_i = carry
        m = jnp.min(cand_d, axis=1)                        # [bq]
        am = jnp.argmin(cand_d, axis=1).astype(jnp.int32)  # [bq]
        sel = col == am[:, None]                           # [bq, k+bn]
        mi = jnp.sum(jnp.where(sel, cand_i, 0), axis=1)    # [bq]
        write = out_col == t
        new_d = jnp.where(write, m[:, None], new_d)
        new_i = jnp.where(write, mi[:, None], new_i)
        cand_d = jnp.where(sel, jnp.inf, cand_d)
        return cand_d, cand_i, new_d, new_i

    _, _, new_d, new_i = jax.lax.fori_loop(
        0, k, body, (cand_d, cand_i, new_d, new_i))
    outd_ref[...] = new_d
    outi_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "precision",
                                             "interpret"))
def l2_topk_padded(q: jax.Array, x: jax.Array, x_sqnorm: jax.Array, *,
                   k: int, bq: int = 128, bn: int = 512,
                   precision: Optional[jax.lax.Precision] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pre-padded fused distance+topk. See ops.l2_topk for the public API.

    `precision` is the MXU contraction precision of the q.x tile (None is
    Mosaic's default; HIGHEST contracts in full f32).

    q: [B, D] (B % bq == 0), x: [N, D] (N % bn == 0), x_sqnorm: [N].
    Returns (dist [B, k] ascending — WITHOUT the ||q||^2 term, idx [B, k]).
    """
    b, d = q.shape
    n = x.shape[0]
    assert b % bq == 0 and n % bn == 0, (b, bq, n, bn)
    grid = (b // bq, n // bn)
    xsq2d = x_sqnorm.reshape(1, n)

    kernel = functools.partial(_l2_topk_kernel, k=k, bn=bn,
                               precision=precision)
    outd, outi = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(q, x, xsq2d)
    return outd, outi
