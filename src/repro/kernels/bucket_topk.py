"""Fused IVF probe step: per-query bucket distances + running top-k merge.

This is DARTH-on-IVF's hot loop (paper §3.3.2): each active query scans
its next bucket [cap, D] and merges into its running top-k. Unlike
l2_topk (one shared DB for all queries), every query here has its OWN
gathered bucket, so the distance work is a batched matvec, not a shared
matmul.

Kernel layout: grid (query tiles [parallel], bucket tiles [arbitrary]).
Per step the kernel holds q [bq, D], one bucket tile [bq, bc, D] with
its squared norms and ids, and the running top-k; dist = ||x||^2 -
2 q.x + bias is an elementwise multiply-reduce on the VPU, followed by
the same K-step masked-min merge as l2_topk. The bucket axis walks
sequentially and keeps the running top-k and the insert count in the
*output* blocks (revisited across it), so VMEM holds one tile whatever
the bucket cap: at bq=8, bc=512, D=128 the f32 tile is 2 MiB (4 MiB
double-buffered) plus a 2 MiB product temporary; int8 tiles are a
quarter of that before the in-VMEM f32 cast.

The per-query additive `bias` generalizes the ||q||^2 term so the SAME
kernel serves both storage formats (ops.py picks the inputs):
  f32:  pass q,        bias = ||q||^2
  SQ8:  pass q*scale,  bias = ||q||^2 - 2 q.offset   (asymmetric dequant:
        ||x_hat - q||^2 = sqn - 2[(q*scale).x8 + q.offset] + ||q||^2)

The kernel also emits the per-query count of bucket distances strictly
below the incoming k-th (`kth`) — the `ninserts` counter DARTH's feature
vector needs — so the sharded probe never computes distances twice.
The count compares against the incoming `kth`, not the running one, so
per-tile counts add up to the untiled count; the merge breaks ties by
position (running top-k first, then bucket order), so the result is the
same as one merge over the whole bucket.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_mode


def _bucket_topk_kernel(q_ref, vecs_ref, sqn_ref, ids_ref, bias_ref, kth_ref,
                        ind_ref, ini_ref, outd_ref, outi_ref, outc_ref,
                        *, k: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        outd_ref[...] = ind_ref[...].astype(jnp.float32)
        outi_ref[...] = ini_ref[...]
        outc_ref[...] = jnp.zeros_like(outc_ref)

    q = q_ref[...].astype(jnp.float32)            # [bq, D]
    vecs = vecs_ref[...].astype(jnp.float32)      # [bq, bc, D]
    sqn = sqn_ref[...].astype(jnp.float32)        # [bq, bc]
    ids = ids_ref[...]                            # [bq, bc]
    bias = bias_ref[...].astype(jnp.float32)      # [bq, 1]
    kth = kth_ref[...].astype(jnp.float32)        # [bq, 1]
    run_d = outd_ref[...]                         # [bq, K]
    run_i = outi_ref[...]                         # [bq, K]

    dots = jnp.sum(vecs * q[:, None, :], axis=2)  # [bq, bc] (VPU reduce)
    dist = sqn - 2.0 * dots + bias
    dist = jnp.where(ids >= 0, jnp.maximum(dist, 0.0), jnp.inf)
    outc_ref[...] += jnp.sum(dist < kth, axis=1,
                             keepdims=True).astype(jnp.int32)

    cand_d = jnp.concatenate([run_d, dist], axis=1)      # [bq, K+bc]
    cand_i = jnp.concatenate([run_i, ids], axis=1)
    col = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)
    out_col = jax.lax.broadcasted_iota(jnp.int32, run_d.shape, 1)
    new_d = jnp.zeros_like(run_d)
    new_i = jnp.zeros_like(run_i)

    def body(t, carry):
        cand_d, cand_i, new_d, new_i = carry
        m = jnp.min(cand_d, axis=1)
        am = jnp.argmin(cand_d, axis=1).astype(jnp.int32)
        sel = col == am[:, None]
        mi = jnp.sum(jnp.where(sel, cand_i, 0), axis=1)
        write = out_col == t
        new_d = jnp.where(write, m[:, None], new_d)
        new_i = jnp.where(write, mi[:, None], new_i)
        cand_d = jnp.where(sel, jnp.inf, cand_d)
        return cand_d, cand_i, new_d, new_i

    _, _, new_d, new_i = jax.lax.fori_loop(
        0, k, body, (cand_d, cand_i, new_d, new_i))
    outd_ref[...] = new_d
    outi_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def bucket_topk_padded(q: jax.Array, vecs: jax.Array, sqn: jax.Array,
                       ids: jax.Array, bias: jax.Array, kth: jax.Array,
                       run_d: jax.Array, run_i: jax.Array,
                       *, bq: int = 8, bc: int = 512,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-padded fused probe. q: [B, D] (B % bq == 0), vecs: [B, C, D]
    (C % bc == 0), sqn/ids: [B, C], bias/kth: [B, 1], run_d/run_i:
    [B, K]. Returns (merged dist [B, K], merged ids [B, K], inserts
    i32[B, 1])."""
    b, d = q.shape
    c = vecs.shape[1]
    k = run_d.shape[1]
    assert b % bq == 0 and c % bc == 0, (b, bq, c, bc)
    kernel = functools.partial(_bucket_topk_kernel, k=k)
    outd, outi, outc = pl.pallas_call(
        kernel,
        grid=(b // bq, c // bc),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, bc, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(q, vecs, sqn, ids, bias, kth, run_d, run_i)
    return outd, outi, outc
