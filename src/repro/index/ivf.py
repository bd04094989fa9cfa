"""IVF index with a step-wise probe API (the shape DARTH drives).

TPU-native layout (DESIGN.md §2): bucket-major padded storage
``[nlist, cap, D]`` — every probe is a fixed-shape gather + batched matvec,
so the whole search is jit/scan/while-able with per-query active masks.

The probe loop exposes exactly the counters DARTH's features need:
``ndis`` advances by the *true* bucket population (padding excluded),
``nstep`` is the probe number, ``firstNN`` is the distance to the nearest
centroid (paper §3.3.2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.padding import (PAD_DIST, PAD_ID, PAD_SQNORM, pad_dists,
                                pad_ids)
from repro.index import kmeans as kmeans_lib


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IVFIndex:
    centroids: jax.Array      # f32[nlist, D]
    bucket_vecs: jax.Array    # f32|int8[nlist, cap, D] (zero padded)
    bucket_ids: jax.Array     # i32[nlist, cap] (-1 padding)
    bucket_sqnorm: jax.Array  # f32[nlist, cap] (+inf padding) — of the
    #                           DEQUANTIZED vectors when SQ8
    bucket_sizes: jax.Array   # i32[nlist]
    # SQ8 affine dequant (x_hat = scale * x8 + offset, per dim); identity
    # (ones/zeros) for f32 storage.
    scale: jax.Array          # f32[D]
    offset: jax.Array         # f32[D]
    # Cold-tier indirection (serve.cold): the bucket arrays above hold
    # only the RESIDENT buckets and hot_map[bucket] names the slot a
    # bucket currently occupies (-1 = spilled to the host cold tier; a
    # probe of a cold bucket is skipped, never stalls). None = every
    # bucket resident at its own slot (bucket id == slot id).
    hot_map: Optional[jax.Array] = None   # i32[nlist]

    @property
    def quantized(self) -> bool:
        return self.bucket_vecs.dtype == jnp.int8

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.bucket_vecs.shape[1]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    @property
    def num_vectors(self) -> int:
        return int(jax.device_get(self.bucket_sizes).sum())


def quantize_sq8(x: np.ndarray, scale: np.ndarray, offset: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Per-dim affine SQ8: returns (int8 codes, dequantized f32,
    clipped-value count).

    ``scale``/``offset`` are usually the FROZEN base range (compaction
    re-quantizes deltas against it so stored codes stay comparable), so
    vectors from an OOD drift burst can exceed it. They are clamped to
    the representable range — correct, but lossy — and the third return
    counts the clamped scalars so callers can surface the loss
    (``darth_sq8_clipped_total``) instead of silently biasing the
    asymmetric distances."""
    raw = np.round((x - offset) / scale)
    nclipped = int(np.count_nonzero((raw < -127.0) | (raw > 127.0)))
    x8 = np.clip(raw, -127, 127).astype(np.int8)
    return x8, x8.astype(np.float32) * scale + offset, nclipped


def pack_buckets(x_store: np.ndarray, x_deq: np.ndarray, ids: np.ndarray,
                 assign: np.ndarray, nlist: int, *, cap_round: int = 8
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Bucket-major padded layout from precomputed assignments.

    `ids` are arbitrary GLOBAL ids (build passes 0..n-1; streaming
    compaction passes the surviving base + delta ids, which keeps ids
    stable across compactions). cap = max bucket size rounded up to
    cap_round; padded slots carry the repo convention vecs 0 / ids -1 /
    sqnorm +inf. Returns (bucket_vecs, bucket_ids, bucket_sqnorm, sizes).
    """
    gen = pack_buckets_steps(x_store, x_deq, ids, assign, nlist,
                             cap_round=cap_round)
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def pack_buckets_steps(x_store: np.ndarray, x_deq: np.ndarray,
                       ids: np.ndarray, assign: np.ndarray, nlist: int, *,
                       cap_round: int = 8, chunk: int = 64):
    """Incremental pack_buckets: one generator, both pack paths.

    Yields after filling each `chunk` of buckets so a background
    compaction (mutate.compact) can bound the work per serve-loop tick;
    pack_buckets drains it in one call for the synchronous build path.
    Returns (bucket_vecs, bucket_ids, bucket_sqnorm, sizes) via
    StopIteration.value.
    """
    d = x_store.shape[1]
    order = np.argsort(assign, kind="stable")
    sizes = np.bincount(assign, minlength=nlist)
    cap = int(max(8, -(-int(max(sizes.max(), 1)) // cap_round) * cap_round))
    bucket_vecs = np.zeros((nlist, cap, d), x_store.dtype)
    bucket_ids = np.full((nlist, cap), PAD_ID, np.int32)
    bucket_sqnorm = np.full((nlist, cap), PAD_SQNORM, np.float32)
    starts = np.concatenate([[0], np.cumsum(sizes)])
    for c0 in range(0, nlist, chunk):
        for c in range(c0, min(nlist, c0 + chunk)):
            sz = int(sizes[c])
            sel = order[starts[c]:starts[c] + sz]
            bucket_vecs[c, :sz] = x_store[sel]
            bucket_ids[c, :sz] = ids[sel]
            bucket_sqnorm[c, :sz] = (x_deq[sel] ** 2).sum(axis=1)
        yield
    return bucket_vecs, bucket_ids, bucket_sqnorm, sizes.astype(np.int32)


def build(x: np.ndarray, nlist: int, *, iters: int = 15, seed: int = 0,
          cap_round: int = 8, quantize: bool = False) -> IVFIndex:
    """Cluster + bucket-major layout. cap = max bucket size rounded up.

    quantize=True stores vectors as SQ8 (per-dim affine int8): 4x less HBM
    at search time with asymmetric (f32-query vs dequantized-db) distances;
    bucket_sqnorm is computed on the dequantized vectors so reported
    distances match what the quantized search actually measures.
    """
    x = np.asarray(x, np.float32)
    n, d = x.shape
    cents = kmeans_lib.kmeans(x, nlist, iters=iters, seed=seed)
    a = np.asarray(kmeans_lib.assign(jnp.asarray(x), jnp.asarray(cents)))

    if quantize:
        lo = x.min(axis=0)
        hi = x.max(axis=0)
        scale = np.maximum((hi - lo) / 254.0, 1e-12).astype(np.float32)
        offset = ((hi + lo) / 2.0).astype(np.float32)
        x_store, x_deq, _ = quantize_sq8(x, scale, offset)
    else:
        scale = np.ones((d,), np.float32)
        offset = np.zeros((d,), np.float32)
        x_store = x
        x_deq = x

    bucket_vecs, bucket_ids, bucket_sqnorm, sizes = pack_buckets(
        x_store, x_deq, np.arange(n, dtype=np.int32), a, nlist,
        cap_round=cap_round)
    return IVFIndex(
        centroids=jnp.asarray(cents),
        bucket_vecs=jnp.asarray(bucket_vecs),
        bucket_ids=jnp.asarray(bucket_ids),
        bucket_sqnorm=jnp.asarray(bucket_sqnorm),
        bucket_sizes=jnp.asarray(sizes),
        scale=jnp.asarray(scale),
        offset=jnp.asarray(offset),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IVFSearchState:
    q: jax.Array            # f32[B, D]
    qsq: jax.Array          # f32[B, 1]
    probe_order: jax.Array  # i32[B, nprobe] ranked centroids
    first_nn: jax.Array     # f32[B] distance to nearest centroid
    probe_pos: jax.Array    # i32[B] next probe
    topk_d: jax.Array       # f32[B, K] ascending (inf = empty)
    topk_i: jax.Array       # i32[B, K] (-1 = empty)
    active: jax.Array       # bool[B]
    ndis: jax.Array         # i32[B] true distance calcs so far
    ninserts: jax.Array     # i32[B] result-set updates so far


def rank_centroids(centroids: jax.Array, qf: jax.Array, qsq: jax.Array,
                   nprobe: int) -> Tuple[jax.Array, jax.Array]:
    """Rank the nprobe closest centroids per query; also returns the
    first-NN distance feature. Shared by init_state and the sharded
    init (dist.collectives pins this top_k inside a batch-axis
    shard_map on a hosts mesh — one definition keeps them in parity)."""
    cd = (jnp.sum(centroids**2, axis=1)[None, :]
          - 2.0 * qf @ centroids.T)                            # [B, nlist]
    neg, order = jax.lax.top_k(-cd, nprobe)
    first_nn = jnp.sqrt(jnp.maximum(-neg[:, 0] + qsq[:, 0], 0.0))
    return order.astype(jnp.int32), first_nn


def fresh_state(qf: jax.Array, qsq: jax.Array, order: jax.Array,
                first_nn: jax.Array, k: int) -> IVFSearchState:
    """Assemble the start-of-search state around a ranked probe order."""
    b = qf.shape[0]
    return IVFSearchState(
        q=qf, qsq=qsq,
        probe_order=order,
        first_nn=first_nn,
        probe_pos=jnp.zeros((b,), jnp.int32),
        topk_d=pad_dists((b, k)),
        topk_i=pad_ids((b, k)),
        active=jnp.ones((b,), bool),
        ndis=jnp.zeros((b,), jnp.int32),
        ninserts=jnp.zeros((b,), jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def init_state(index: IVFIndex, q: jax.Array, *, k: int,
               nprobe: int) -> IVFSearchState:
    qf = q.astype(jnp.float32)
    qsq = jnp.sum(qf**2, axis=1, keepdims=True)
    order, first_nn = rank_centroids(index.centroids, qf, qsq, nprobe)
    return fresh_state(qf, qsq, order, first_nn, k)


@jax.jit
def probe_step(index: IVFIndex, s: IVFSearchState) -> IVFSearchState:
    """Scan one bucket per active query; merge global top-k; bump counters."""
    b, k = s.topk_d.shape
    nprobe = s.probe_order.shape[1]
    pos = jnp.minimum(s.probe_pos, nprobe - 1)
    bucket = jnp.take_along_axis(s.probe_order, pos[:, None], axis=1)[:, 0]

    if index.hot_map is not None:
        # Cold tier: resolve bucket -> resident slot; a cold bucket
        # (slot -1) is SKIPPED this probe — the position still
        # advances, its candidates and ndis are masked out — so a cold
        # hit never stalls the fixed-shape step (serve.cold prefetches
        # ahead of the probe order to make misses rare).
        slot = index.hot_map[bucket]        # [B]
        hot = slot >= 0
        slot = jnp.maximum(slot, 0)
    else:
        slot = bucket
        hot = None
    vecs = index.bucket_vecs[slot]          # [B, cap, D] (f32 or int8)
    ids = index.bucket_ids[slot]            # [B, cap]
    sqn = index.bucket_sqnorm[slot]         # [B, cap]
    sizes = index.bucket_sizes[bucket]      # [B] (full per-bucket sizes)

    if index.quantized:
        # asymmetric SQ8: q . x_hat = (q*scale) . x8 + q . offset
        qa = s.q * index.scale[None, :]
        dots = (jnp.einsum("bd,bcd->bc", qa, vecs.astype(jnp.float32))
                + (s.q @ index.offset)[:, None])
    else:
        dots = jnp.einsum("bd,bcd->bc", s.q, vecs)
    dist = sqn - 2.0 * dots + s.qsq
    dist = jnp.where(ids >= 0, jnp.maximum(dist, 0.0), PAD_DIST)
    # Inactive queries contribute nothing.
    dist = jnp.where(s.active[:, None], dist, PAD_DIST)
    if hot is not None:
        dist = jnp.where(hot[:, None], dist, PAD_DIST)
        sizes = jnp.where(hot, sizes, 0)

    old_kth = s.topk_d[:, -1]
    cand_d = jnp.concatenate([s.topk_d, dist], axis=1)
    cand_i = jnp.concatenate([s.topk_i, ids], axis=1)
    neg, sel = jax.lax.top_k(-cand_d, k)
    new_d = -neg
    new_i = jnp.take_along_axis(cand_i, sel, axis=1)

    inserts = jnp.sum(dist < old_kth[:, None], axis=1).astype(jnp.int32)
    inserts = jnp.minimum(inserts, k)
    done_probes = s.probe_pos + s.active.astype(jnp.int32)
    return IVFSearchState(
        q=s.q, qsq=s.qsq, probe_order=s.probe_order, first_nn=s.first_nn,
        probe_pos=done_probes,
        topk_d=jnp.where(s.active[:, None], new_d, s.topk_d),
        topk_i=jnp.where(s.active[:, None], new_i, s.topk_i),
        active=s.active & (done_probes < nprobe),
        ndis=s.ndis + jnp.where(s.active, sizes, 0).astype(jnp.int32),
        ninserts=s.ninserts + jnp.where(s.active, inserts, 0),
    )


def _drive(step, index: IVFIndex, s: IVFSearchState
           ) -> Tuple[jax.Array, jax.Array, IVFSearchState]:
    """Run a probe step to natural termination (all probes exhausted)."""
    s = jax.lax.while_loop(lambda s: s.active.any(),
                           lambda s: step(index, s), s)
    return s.topk_d, s.topk_i, s


def search(index: IVFIndex, q: jax.Array, *, k: int,
           nprobe: int) -> Tuple[jax.Array, jax.Array, IVFSearchState]:
    """Plain (no early termination) IVF search: scan all nprobe buckets."""
    return _drive(probe_step, index, init_state(index, q, k=k, nprobe=nprobe))


def search_sharded(index: IVFIndex, q: jax.Array, *, k: int, nprobe: int,
                   mesh, use_kernel: bool = True,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array, IVFSearchState]:
    """Plain IVF search through the shard_map probe step: `index` must be
    placed with dist.place_index(index, mesh) (cap dim split over the
    "model" axis). Numerically matches `search` on any shard count."""
    from repro.dist import collectives  # local import: dist uses kernels

    step = collectives.make_sharded_probe_step(
        mesh, use_kernel=use_kernel, interpret=interpret)
    return _drive(step, index, init_state(index, q, k=k, nprobe=nprobe))
