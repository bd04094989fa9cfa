"""Public jit'd wrappers for the Pallas kernels: padding and norm handling.

Every wrapper takes ``interpret=None``, which kernels.backend resolves by
backend: compiled by Mosaic on a TPU, run in the Pallas interpreter on the
CPU (tests and rehearsals), an error anywhere else. Passing ``True`` or
``False`` overrides that explicitly. The pure-jnp oracles in ``ref.py``
are test references only; no serving path calls them.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.gbdt.model import GBDTParams
from repro.kernels.bucket_topk import bucket_topk_padded
from repro.kernels.gbdt_predict import gbdt_predict_padded
from repro.kernels.l2_topk import l2_topk_padded


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "precision",
                                             "interpret"))
def l2_topk(q: jax.Array, x: jax.Array, *, k: int,
            x_sqnorm: Optional[jax.Array] = None,
            bias: Optional[jax.Array] = None,
            bq: int = 128, bn: int = 512,
            precision: Optional[jax.lax.Precision] = None,
            interpret: Optional[bool] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k nearest (squared L2). Handles padding; returns true
    squared distances, ascending, with int32 ids; padded/invalid slots
    have dist=+inf, id=-1.

    ``bias`` [B, 1] is the per-query constant added to the kernel's
    ``x_sqnorm - 2 q.x`` partial distances; it defaults to ``||q||^2``
    (exact f32). The SQ8 asymmetric form — mirroring ``bucket_probe`` —
    passes ``q*scale`` as ``q``, int8 codes as ``x``, the DEQUANTIZED
    sqnorms, and ``bias = ||q||^2 - 2 q.offset``."""
    b, d = q.shape
    n = x.shape[0]
    if x_sqnorm is None:
        x_sqnorm = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)
    if bias is None:
        bias = jnp.sum(q.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    bq_eff = min(bq, _round_up(b, 8))
    bn_eff = min(bn, _round_up(n, 128))
    bp = _round_up(b, bq_eff)
    np_ = _round_up(n, bn_eff)
    qp = jnp.pad(q, ((0, bp - b), (0, 0)))
    xp = jnp.pad(x, ((0, np_ - n), (0, 0)))
    xsqp = jnp.pad(x_sqnorm, (0, np_ - n), constant_values=jnp.inf)
    dist, idx = l2_topk_padded(qp, xp, xsqp, k=k, bq=bq_eff, bn=bn_eff,
                               precision=precision, interpret=interpret)
    dist = dist[:b] + bias
    idx = idx[:b]
    dist = jnp.where(idx >= 0, jnp.maximum(dist, 0.0), jnp.inf)
    return dist, idx


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def gbdt_predict(params: GBDTParams, x: jax.Array, *, bq: int = 64,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Batched ensemble inference via the Pallas kernel. x: [B, F] -> [B]."""
    b, f = x.shape
    bq_eff = min(bq, _round_up(b, 8))
    bp = _round_up(b, bq_eff)
    xp = jnp.pad(x, ((0, bp - b), (0, 0)))
    out = gbdt_predict_padded(xp, params.feat, params.thresh, params.leaf,
                              bq=bq_eff, interpret=interpret)
    return params.base + out[:b]


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def bucket_topk(q: jax.Array, vecs: jax.Array, sqn: jax.Array,
                ids: jax.Array, run_d: jax.Array, run_i: jax.Array, *,
                bq: int = 8, bc: int = 512,
                interpret: Optional[bool] = None):
    """Fused IVF probe step (per-query bucket + running top-k merge)."""
    bias = jnp.sum(q.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    d, i, _ = bucket_probe(q, vecs, sqn, ids, bias, run_d[:, -1:],
                           run_d, run_i, bq=bq, bc=bc, interpret=interpret)
    return d, i


def bucket_tile(c: int, bc: int) -> int:
    """Bucket tile width for a cap of `c` columns: the whole bucket when
    it fits in one tile of `bc`, else as few tiles as tiles of `bc` would
    take, each narrowed to the multiple of 128 (the lane width a tile of
    a [B, C] array needs) that still covers `c`. The tile count is kept
    because each grid step costs more than the padding it would save: on
    a TPU v5e at C=1560, 4 tiles of 512 (padded to 2048) ran in 2.24 ms
    where 13 tiles of 128 (padded to 1664) took 2.93 ms."""
    if c <= bc:
        return c
    tiles = -(-c // bc)
    return _round_up(-(-c // tiles), 128)


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def bucket_probe(q: jax.Array, vecs: jax.Array, sqn: jax.Array,
                 ids: jax.Array, bias: jax.Array, kth: jax.Array,
                 run_d: jax.Array, run_i: jax.Array, *,
                 bq: int = 8, bc: int = 512,
                 interpret: Optional[bool] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused probe with explicit per-query bias + insert counting.

    dist = sqn - 2 q.vecs + bias  (bias = ||q||^2 for f32 storage; the SQ8
    asymmetric form passes q*scale and bias = ||q||^2 - 2 q.offset).
    Returns (merged dist [B, K], merged ids [B, K], inserts i32[B]) where
    inserts counts bucket distances strictly below `kth` [B, 1].

    A bucket wider than `bc` (a multiple of 128) is scanned in tiles of
    at most `bc` columns, padded up to a whole number of tiles with the
    padding contract (ids -1, sqnorm +inf); see `bucket_tile`."""
    b, c = ids.shape
    bq_eff = min(bq, _round_up(b, 4))
    bc_eff = bucket_tile(c, bc)
    pad_b = _round_up(b, bq_eff) - b
    pad_c = _round_up(c, bc_eff) - c
    if pad_b or pad_c:
        q = jnp.pad(q, ((0, pad_b), (0, 0)))
        vecs = jnp.pad(vecs, ((0, pad_b), (0, pad_c), (0, 0)))
        sqn = jnp.pad(sqn, ((0, pad_b), (0, pad_c)), constant_values=jnp.inf)
        ids = jnp.pad(ids, ((0, pad_b), (0, pad_c)), constant_values=-1)
        bias = jnp.pad(bias, ((0, pad_b), (0, 0)))
        kth = jnp.pad(kth, ((0, pad_b), (0, 0)))
        run_d = jnp.pad(run_d, ((0, pad_b), (0, 0)), constant_values=jnp.inf)
        run_i = jnp.pad(run_i, ((0, pad_b), (0, 0)), constant_values=-1)
    d, i, cnt = bucket_topk_padded(q, vecs, sqn, ids, bias, kth, run_d,
                                   run_i, bq=bq_eff, bc=bc_eff,
                                   interpret=interpret)
    return d[:b], i[:b], cnt[:b, 0]
