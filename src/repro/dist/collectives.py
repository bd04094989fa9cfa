"""Cross-shard search collectives: the database is row-sharded over the
"model" mesh axis, each shard runs the fused l2_topk kernel on its local
rows, and the per-shard candidates are merged with one small all-gather —
collective volume O(B * k * shards * 8 bytes), independent of N.

Three sharded entry points:
  * make_sharded_flat_search — exact flat k-NN over a row-sharded [N, D]
    database (ground truth / brute-force baseline).
  * make_sharded_probe_step — one IVF probe over a CAP-sharded bucket
    store [nlist, cap, D] (dist.sharding.place_index splits the cap dim
    over "model"): each shard scans its local slice of the probed bucket
    with the fused bucket_topk kernel, candidates merge via one tiled
    [B, k] all-gather + merge_topk, insert counters psum. Per-probe
    traffic drops from the GSPMD gather's O(B*cap*D) to O(B*k*shards).
  * make_sharded_beam_step — one HNSW beam expansion over a ROW-sharded
    graph (dist.sharding.place_index splits vectors/sqnorm/neighbors on
    the node dim over "model"; the per-query visited structure — exact
    [B, N] bitmap or fixed-width hashed filter [B, W] — splits on its
    second dim too): the shard owning each query's selected
    candidate resolves its adjacency row (one [B, M] psum), every shard
    scans the neighbors IT owns against its local vectors/visited slice,
    and the per-shard [B, M] distance frontiers merge via one tiled
    all-gather + positional min + top-k. Per-step traffic drops from the
    GSPMD gather's O(B*M*D) to O(B*M*shards), independent of N and D.

Padding contract: the sharded dim (N rows / bucket cap / graph nodes) is
padded up to a multiple of the shard count; padded slots carry
sqnorm = +inf so they can never enter a top-k, padded ids (bucket_ids /
neighbors rows) are -1, and any slot whose distance is +inf reports
id -1 (same convention as index/flat.py, index/ivf.py, index/hnsw.py).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.padding import (PAD_DIST, PAD_ID, PAD_SQNORM, pad_dists,
                                pad_ids)
from repro.kernels import ops

SHARD_AXIS = "model"
_EXACT = jax.lax.Precision.HIGHEST

# Slot-pool serving (serve.engine.DarthServer on a make_serve_mesh):
# when the mesh carries a "hosts" axis, the search state's slot (batch)
# dim splits over it inside the probe/beam shard_maps, so each host
# group's devices step only the slot slice its host loop owns. The
# "model"-axis collectives then run WITHIN a host group — the per-chunk
# all-gather/psum operands shrink from [B, ..] to [B/hosts, ..]. Absent
# the axis, the spec entry is None and the programs are unchanged.
#
# Slot-dim top-k pinning: jax.lax.top_k lowers to a TopK custom-call,
# which the GSPMD partitioner cannot split — any top-k over the
# hosts-split slot dim that runs OUTSIDE a shard_map forces the
# partitioner to all-gather its operand across host groups first (the
# replicated-frontier reshard the ROADMAP flagged at ~1.12x). Both
# sharded steps therefore run their candidate merges INSIDE a shard_map
# whose batch spec is P(BATCH_AXIS, ...), so the custom-call only ever
# sees each host group's local slot rows (benchmarks/dist_search.py
# dist_multi_host_serve gates the resulting per-chunk byte win).
BATCH_AXIS = "hosts"


def shard_count(mesh: Mesh, axis: str = SHARD_AXIS) -> int:
    """Size of `axis` on `mesh` (1 when the mesh lacks the axis)."""
    return int(mesh.shape[axis]) if axis in mesh.axis_names else 1


def _batch_axis(mesh: Mesh) -> "str | None":
    return BATCH_AXIS if BATCH_AXIS in mesh.axis_names else None


def merge_topk(cand_d: jax.Array, cand_i: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Merge [B, M] candidate (dist, id) lists to the best k per row.
    +inf candidates (shard padding) are masked back to id -1."""
    neg, pos = jax.lax.top_k(-cand_d, k)
    d = -neg
    i = jnp.take_along_axis(cand_i, pos, axis=1)
    return d, jnp.where(jnp.isfinite(d), i, PAD_ID)


def make_sharded_flat_search(mesh: Mesh, k: int, *, axis: str = SHARD_AXIS,
                             use_kernel: bool = True,
                             interpret: Optional[bool] = None
                             ) -> Callable[[jax.Array, jax.Array],
                                           Tuple[jax.Array, jax.Array]]:
    """Exact flat k-NN over a database sharded on `axis`.

    Returns fn(q [B, D], x [N, D]) -> (dist [B, k] ascending, idx [B, k]),
    numerically matching index.flat.search on any shard count (including
    the 1-device host mesh). Queries are replicated; per-shard local
    top-k uses the fused Pallas kernel, the cross-shard merge is one
    tiled all-gather of [B, k] + top_k. This is ground truth, so the
    q.x contraction runs at HIGHEST precision on either path.
    """
    nshards = shard_count(mesh, axis)

    def local_topk(q, x_loc, sqn_loc):
        if use_kernel:
            d_loc, i_loc = ops.l2_topk(q, x_loc, k=k, x_sqnorm=sqn_loc,
                                       precision=_EXACT,
                                       interpret=interpret)
        else:  # pure-XLA: padded rows enter with sqn=+inf, never win
            qf = q.astype(jnp.float32)
            d2 = (jnp.sum(qf ** 2, 1)[:, None] + sqn_loc[None, :]
                  - 2.0 * jnp.matmul(qf, x_loc.astype(jnp.float32).T,
                                     precision=_EXACT))
            if d2.shape[1] < k:  # fewer local rows than k: pad candidates
                d2 = jnp.pad(d2, ((0, 0), (0, k - d2.shape[1])),
                             constant_values=PAD_DIST)
            neg, i_loc = jax.lax.top_k(-d2, k)
            d_loc = jnp.maximum(-neg, 0.0)
        rows = x_loc.shape[0]
        base = jax.lax.axis_index(axis) * rows
        i_glob = jnp.where(jnp.isfinite(d_loc) & (i_loc >= 0),
                           i_loc + base, PAD_ID)
        cand_d = jax.lax.all_gather(d_loc, axis, axis=1, tiled=True)
        cand_i = jax.lax.all_gather(i_glob, axis, axis=1, tiled=True)
        return merge_topk(cand_d, cand_i, k)

    sharded = shard_map(
        local_topk, mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis)),
        out_specs=(P(), P()),
        check_vma=False)

    @jax.jit
    def search(q: jax.Array, x: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
        n = x.shape[0]
        per_shard = -(-n // nshards)
        pad = per_shard * nshards - n
        sqn = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        sqnp = jnp.pad(sqn, (0, pad), constant_values=PAD_SQNORM)
        return sharded(q, xp, sqnp)

    return search


# Keyed on the mesh GEOMETRY + device ids, not the Mesh object: a Mesh
# key would hold the mesh (and through jit caches, its device buffers)
# alive across tests, and two equivalent meshes would compile twice.
# Equivalent-mesh hits reuse the first mesh's compiled fn — same axes
# over the same devices in the same order means identical placement;
# meshes over different device subsets get their own entries.
_SEARCH_CACHE: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()
_SEARCH_CACHE_MAX = 8


def _mesh_key(mesh: Mesh) -> tuple:
    return (tuple(mesh.shape.items()),              # ordered (axis, size)
            tuple(d.id for d in mesh.devices.flat))


def _memoized(cache: "collections.OrderedDict[tuple, Callable]", key: tuple,
              build: Callable[[], Callable]) -> Callable:
    """Shared LRU memo for the jitted sharded-step builders."""
    fn = cache.get(key)
    if fn is None:
        while len(cache) >= _SEARCH_CACHE_MAX:
            cache.popitem(last=False)
        fn = cache[key] = build()
    else:
        cache.move_to_end(key)
    return fn


def _cached_search(mesh: Mesh, k: int):
    return _memoized(_SEARCH_CACHE, (_mesh_key(mesh), k),
                     lambda: make_sharded_flat_search(mesh, k))


def sharded_flat_search(q: jax.Array, x: jax.Array, k: int, mesh: Mesh
                        ) -> Tuple[jax.Array, jax.Array]:
    """One-shot convenience wrapper (builds + caches the jitted fn)."""
    return _cached_search(mesh, k)(q, x)


# ---------------------------------------------------------------------------
# Sharded IVF probe
# ---------------------------------------------------------------------------

# Same geometry-keyed caching rationale as _SEARCH_CACHE: a fresh jitted
# step per call would defeat jit's function-identity cache and recompile
# the shard_map program on every search_sharded invocation.
_PROBE_CACHE: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()

_INIT_CACHE: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()


def make_sharded_ivf_init(mesh: Mesh, *, axis: str = SHARD_AXIS
                          ) -> Callable[..., Any]:
    """IVF search-state init with the probe-order ranking PINNED.

    ivf.init_state ranks centroids with a jax.lax.top_k over [B, nlist]
    — an unpartitionable TopK custom-call. Inside the server's init
    chunk the slot dim B is hosts-split, so the plain init forces GSPMD
    to all-gather the centroid distances across host groups before the
    ranking (the same bug class the step merges' pin_merge fixed; the
    analysis gate's unpartitionable-topk pass caught this one). Running
    ivf.rank_centroids inside a batch-axis shard_map keeps the ranking
    on each host group's local slot rows. Bookkeeping and results are
    bit-identical to ivf.init_state on any mesh; without a hosts axis
    the shard_map is skipped entirely and this IS ivf.init_state.
    """
    from repro.index import ivf as ivf_lib

    key = (_mesh_key(mesh), axis)
    bh = _batch_axis(mesh)

    def init(index: Any, q: jax.Array, *, k: int, nprobe: int) -> Any:
        qf = q.astype(jnp.float32)
        qsq = jnp.sum(qf ** 2, axis=1, keepdims=True)
        if bh is None:
            order, first_nn = ivf_lib.rank_centroids(
                index.centroids, qf, qsq, nprobe)
        else:
            rank = shard_map(
                lambda c, qf_loc, qsq_loc: ivf_lib.rank_centroids(
                    c, qf_loc, qsq_loc, nprobe),
                mesh=mesh,
                in_specs=(P(None, None), P(bh, None), P(bh, None)),
                out_specs=(P(bh, None), P(bh)),
                check_vma=False)
            order, first_nn = rank(index.centroids, qf, qsq)
        return ivf_lib.fresh_state(qf, qsq, order, first_nn, k)

    return _memoized(_INIT_CACHE, key,
                     lambda: jax.jit(init,
                                     static_argnames=("k", "nprobe")))


def make_sharded_probe_step(mesh: Mesh, *, axis: str = SHARD_AXIS,
                            use_kernel: bool = True,
                            interpret: Optional[bool] = None,
                            pin_merge: bool = True
                            ) -> Callable[[Any, Any], Any]:
    """One IVF probe step over a cap-sharded bucket store.

    Returns step(index, state) -> state, a drop-in replacement for
    index.ivf.probe_step when the index was placed with
    dist.sharding.place_index(index, mesh): bucket_vecs [nlist, cap, D],
    bucket_ids / bucket_sqnorm [nlist, cap] are split on the cap dim over
    `axis`; centroids, bucket_sizes and the SQ8 dequant tables replicate.

    Per shard the probed bucket's local slice [B, cap/S, D] is scanned
    with the fused bucket_topk kernel (pure-XLA fallback when
    use_kernel=False) into per-shard top-k candidates; the only
    cross-shard traffic is one tiled [B, k] all-gather of (dist, id)
    pairs + an insert-count psum. Bookkeeping (probe cursor, active
    masks, ndis from the replicated bucket_sizes) is replicated and
    identical to the single-device step, so results match
    index.ivf.search exactly on any shard count. A cold-tier store
    (index.hot_map set, serve.cold) resolves bucket ids to device
    slots through the replicated map first; cold buckets skip with the
    same semantics as index.ivf.probe_step and add no collective.

    `pin_merge` keeps the running-top-k merge (a jax.lax.top_k, i.e. an
    unpartitionable TopK custom-call) INSIDE the shard_map so it runs on
    each host group's local slot rows; False restores the pre-pinning
    layout (merge outside the shard_map, forcing a cross-host gather of
    the [B, k + k*shards] candidate array when the mesh has a hosts
    axis) so benchmarks can measure the before/after traffic. The two
    layouts are numerically identical.
    """
    key = (_mesh_key(mesh), axis, use_kernel, interpret, pin_merge)
    nshards = shard_count(mesh, axis)
    bh = _batch_axis(mesh)

    def probe_step(index: Any, s: Any) -> Any:
        b, k = s.topk_d.shape
        nprobe = s.probe_order.shape[1]
        cap = index.bucket_vecs.shape[1]
        if cap % nshards:
            raise ValueError(
                f"bucket cap {cap} not divisible by {nshards} shards; "
                f"place the index with dist.place_index(index, mesh) "
                f"(it pads cap to a shard multiple)")
        pos = jnp.minimum(s.probe_pos, nprobe - 1)
        bucket = jnp.take_along_axis(s.probe_order, pos[:, None],
                                     axis=1)[:, 0]
        sizes = index.bucket_sizes[bucket]       # replicated [B]
        if index.hot_map is not None:
            # Cold-tier store (serve.cold): bucket ids resolve through
            # the replicated hot map to device store slots. A cold
            # bucket (slot -1) is skipped THIS step — the probe cursor
            # still advances, the scan contributes no candidates and
            # the masked sizes keep ndis honest. No extra collective.
            slot = index.hot_map[bucket]
            hot = slot >= 0
            slot = jnp.maximum(slot, 0)
            sizes = jnp.where(hot, sizes, 0)
        else:
            slot = bucket
            hot = jnp.ones_like(bucket, dtype=bool)

        if index.quantized:
            # asymmetric SQ8 via the kernel's bias term:
            # ||x_hat - q||^2 = sqn - 2[(q*scale).x8 + q.offset] + ||q||^2
            q_eff = s.q * index.scale[None, :]
            bias = s.qsq - 2.0 * (s.q @ index.offset)[:, None]
        else:
            q_eff = s.q
            bias = s.qsq
        def scan(q_eff, bias, topk_d, topk_i, slot, hot, vecs, sqn, ids):
            # Local batch size, NOT the outer b: with a "hosts" batch
            # axis each host group scans only its slot slice.
            bl = q_eff.shape[0]
            kth = topk_d[:, -1:]
            v = vecs[slot]                       # [Bl, capS, D] local gather
            # Cold (unresident) buckets degrade to the padding contract
            # (ids -1 / sqnorm +inf): no candidate, no insert count.
            sq = jnp.where(hot[:, None], sqn[slot], PAD_SQNORM)
            id_ = jnp.where(hot[:, None], ids[slot], PAD_ID)
            if use_kernel:
                run_d = pad_dists((bl, k))
                run_i = pad_ids((bl, k))
                d_loc, i_loc, cnt = ops.bucket_probe(
                    q_eff, v, sq, id_, bias, kth, run_d, run_i,
                    interpret=interpret)
            else:
                dist = (sq.astype(jnp.float32)
                        - 2.0 * jnp.einsum("bd,bcd->bc", q_eff,
                                           v.astype(jnp.float32))
                        + bias)
                dist = jnp.where(id_ >= 0, jnp.maximum(dist, 0.0), PAD_DIST)
                cnt = jnp.sum(dist < kth, axis=1).astype(jnp.int32)
                if dist.shape[1] < k:   # tiny shard slice: pad candidates
                    pad = k - dist.shape[1]
                    dist = jnp.pad(dist, ((0, 0), (0, pad)),
                                   constant_values=PAD_DIST)
                    id_ = jnp.pad(id_, ((0, 0), (0, pad)),
                                  constant_values=PAD_ID)
                neg, sel = jax.lax.top_k(-dist, k)
                d_loc = -neg
                i_loc = jnp.take_along_axis(id_, sel, axis=1)
            i_loc = jnp.where(jnp.isfinite(d_loc), i_loc, PAD_ID)
            cand_d = jax.lax.all_gather(d_loc, axis, axis=1, tiled=True)
            cand_i = jax.lax.all_gather(i_loc, axis, axis=1, tiled=True)
            if not pin_merge:
                return cand_d, cand_i, jax.lax.psum(cnt, axis)
            # Merge INSIDE the shard_map: the TopK custom-call then only
            # sees this host group's slot rows (see BATCH_AXIS note).
            # Replicated across `axis` within a host group — every
            # device holds the full gathered candidates, same values.
            new_d, new_i = merge_topk(
                jnp.concatenate([topk_d, cand_d], axis=1),
                jnp.concatenate([topk_i, cand_i], axis=1), k)
            return new_d, new_i, jax.lax.psum(cnt, axis)

        sharded = shard_map(
            scan, mesh=mesh,
            in_specs=(P(bh, None), P(bh, None), P(bh, None), P(bh, None),
                      P(bh), P(bh), P(None, axis, None), P(None, axis),
                      P(None, axis)),
            out_specs=(P(bh, None), P(bh, None), P(bh)),
            check_vma=False)
        out_d, out_i, cnt = sharded(
            q_eff, bias, s.topk_d, s.topk_i, slot, hot,
            index.bucket_vecs, index.bucket_sqnorm, index.bucket_ids)

        if pin_merge:
            new_d, new_i = out_d, out_i
        else:
            new_d, new_i = merge_topk(
                jnp.concatenate([s.topk_d, out_d], axis=1),
                jnp.concatenate([s.topk_i, out_i], axis=1), k)
        inserts = jnp.minimum(cnt, k)
        done_probes = s.probe_pos + s.active.astype(jnp.int32)
        return dataclasses.replace(
            s,
            probe_pos=done_probes,
            topk_d=jnp.where(s.active[:, None], new_d, s.topk_d),
            topk_i=jnp.where(s.active[:, None], new_i, s.topk_i),
            active=s.active & (done_probes < nprobe),
            ndis=s.ndis + jnp.where(s.active, sizes, 0).astype(jnp.int32),
            ninserts=s.ninserts + jnp.where(s.active, inserts, 0),
        )

    # Jitted with the index as an ARGUMENT (not a closure constant):
    # closure-captured consts drop their committed cap-axis sharding, and
    # the whole bucket store would be re-laid-out replicated per device.
    return _memoized(_PROBE_CACHE, key, lambda: jax.jit(probe_step))


# ---------------------------------------------------------------------------
# Sharded HNSW beam step
# ---------------------------------------------------------------------------

_BEAM_CACHE: "collections.OrderedDict[tuple, Callable]" = \
    collections.OrderedDict()


def make_sharded_beam_step(mesh: Mesh, *, axis: str = SHARD_AXIS,
                           pin_merge: bool = True) -> Callable[..., Any]:
    """One HNSW beam expansion over a row-sharded graph.

    Returns step(index, state, k=..) -> state, a drop-in replacement for
    index.hnsw.beam_step when the index was placed with
    dist.sharding.place_index(index, mesh): vectors [N, D], sqnorm [N]
    and neighbors [N, M] are split on the node dim over `axis` (N padded
    to a shard multiple; pad rows carry sqnorm +inf / neighbor ids -1),
    and the search state's visited bitmap [B, N] splits on its node dim.

    Frontier bookkeeping (cand_d / cand_i / cand_exp, [B, ef]) stays
    replicated and identical to the single-device step. Per step, under
    one shard_map:

      1. the shard owning each query's selected candidate contributes
         its adjacency row; a [B, M] psum reconstructs the global
         neighbor-id frontier on every shard,
      2. each shard resolves the neighbors IT owns against its local
         visited slice and vectors (gather + batched distance), masking
         everything else to +inf, and updates its visited slice,
      3. the per-shard [B, M] masked distances merge via one tiled
         all-gather; a positional min over the shard dim restores the
         exact single-device candidate layout (each neighbor is owned by
         exactly one shard), so the ef-merge top-k breaks ties exactly
         like index.hnsw.beam_step and results (topk_d / topk_i / ndis /
         ninserts) match bit-for-bit on any shard count.

    Cross-shard traffic is one [B, M] i32 psum + one [B, M] f32
    all-gather per step — O(B*M*shards) bytes, independent of N and D,
    versus the O(B*M*D) vector gather GSPMD emits for the unsharded
    step on a mesh-placed index.

    When the state carries a HASHED visited filter [B, W] (W < N,
    hnsw.init_state's visited_width; W must be a shard-count multiple)
    step 2 resolves membership at the hash slot's owner instead: one
    extra [B, M] i32 psum rebuilds the global seen mask, keeping the
    per-step traffic N-independent, and the skip behaviour (including
    hash-collision false positives) matches the single-device hashed
    step bit-for-bit. SQ8-resident graphs (int8 vectors) just cast the
    gathered rows — the state's effective query / bias fold the dequant
    transform, so the collective layout is unchanged.

    `pin_merge` runs the frontier merge's top-k (hnsw.frontier_topk, an
    unpartitionable TopK custom-call) inside a batch-axis shard_map so
    it stays on each host group's local slot rows; False restores the
    pre-pinning layout (merge outside, forcing a cross-host gather of
    the [B, ef + M] frontier on a hosts mesh). Numerically identical
    either way — the shard_map wraps the very same frontier_topk.
    """
    key = (_mesh_key(mesh), axis, pin_merge)
    nshards = shard_count(mesh, axis)
    bh = _batch_axis(mesh)

    def local_frontier_topk(cand_d, cand_i, cand_e, ef):
        from repro.index import hnsw as hnsw_lib
        fn = shard_map(
            lambda d, i, e: hnsw_lib.frontier_topk(d, i, e, ef),
            mesh=mesh,
            in_specs=(P(bh, None), P(bh, None), P(bh, None)),
            out_specs=(P(bh, None), P(bh, None), P(bh, None)),
            check_vma=False)
        return fn(cand_d, cand_i, cand_e)

    def beam_step(index: Any, s: Any, *, k: int) -> Any:
        from repro.index import hnsw as hnsw_lib

        b = s.cand_d.shape[0]
        mdeg = index.degree
        if index.num_vectors % nshards:
            raise ValueError(
                f"graph has {index.num_vectors} rows, not divisible by "
                f"{nshards} shards; place the index with "
                f"dist.place_index(index, mesh) (it pads the node dim)")
        # Exact [B, N] bitmap or fixed-width hashed filter [B, W]: the
        # structure is whatever init_state built (static at trace time);
        # either way the visited dim splits over `axis`.
        width = s.visited.shape[1]
        hashed = width < index.num_vectors
        if width % nshards:
            raise ValueError(
                f"visited width {width} not divisible by {nshards} "
                f"shards; pick a power-of-two visited_width that the "
                f"shard count divides")

        # Replicated frontier bookkeeping — shared with hnsw.beam_step
        # so the two steps cannot drift out of parity.
        sel_id_safe, act, cand_exp = hnsw_lib.select_expand(s)

        def expand(q, qsq, sel_id, act, vec_loc, sqn_loc, nbr_loc, vis_loc):
            # Local batch size, NOT the outer b: with a "hosts" batch
            # axis each host group expands only its slot slice.
            bl = q.shape[0]
            rows = vec_loc.shape[0]
            base = jax.lax.axis_index(axis) * rows
            # 1. owner of the selected node contributes its adjacency row
            own_sel = (sel_id >= base) & (sel_id < base + rows)
            sel_loc = jnp.clip(sel_id - base, 0, rows - 1)
            nbrs = jax.lax.psum(
                jnp.where(own_sel[:, None], nbr_loc[sel_loc] + 1, 0),
                axis) - 1                                    # [Bl, M] global
            # 2. scan the neighbors this shard owns
            valid = (nbrs >= 0) & act[:, None]
            owned = valid & (nbrs >= base) & (nbrs < base + rows)
            loc = jnp.where(owned, nbrs - base, 0)
            if hashed:
                # Hashed filter: membership lives at the HASH SLOT's
                # owner, not the vector row's. The slot owner reads its
                # local filter slice and one [Bl, M] i32 psum rebuilds
                # the global seen mask (each slot has exactly one
                # owner), matching hnsw.beam_step's hashed read
                # bit-for-bit — collisions skip the same nodes. The
                # slot owner then sets the bits for every VALID
                # neighbor, as the single-device step does.
                from repro.index import hnsw as hnsw_lib
                slots = hnsw_lib.hash_slot(jnp.maximum(nbrs, 0), width)
                rows_v = vis_loc.shape[1]
                base_v = jax.lax.axis_index(axis) * rows_v
                own_slot = (slots >= base_v) & (slots < base_v + rows_v)
                loc_slot = jnp.where(own_slot, slots - base_v, 0)
                hit = jnp.take_along_axis(vis_loc, loc_slot, axis=1)
                seen = jax.lax.psum(
                    (hit & own_slot).astype(jnp.int32), axis) > 0
                vis_loc = vis_loc.at[
                    jnp.arange(bl)[:, None], loc_slot].max(
                        own_slot & valid)
            else:
                seen = jnp.take_along_axis(vis_loc, loc, axis=1)
                vis_loc = vis_loc.at[
                    jnp.arange(bl)[:, None], loc].max(owned)
            new = owned & ~seen
            vecs = vec_loc[loc].astype(jnp.float32)          # [Bl, M, D]
            dist = (sqn_loc[loc]
                    - 2.0 * jnp.einsum("bd,bmd->bm", q, vecs) + qsq)
            dist = jnp.where(new, jnp.maximum(dist, 0.0), PAD_DIST)
            # 3. merge the masked per-shard frontiers
            dist_all = jax.lax.all_gather(dist, axis, axis=1, tiled=True)
            return nbrs, dist_all, vis_loc

        sharded = shard_map(
            expand, mesh=mesh,
            in_specs=(P(bh, None), P(bh, None), P(bh), P(bh),
                      P(axis, None), P(axis), P(axis, None), P(bh, axis)),
            out_specs=(P(bh, None), P(bh, None), P(bh, axis)),
            check_vma=False)
        nbrs, dist_all, visited = sharded(
            s.q, s.qsq, sel_id_safe, act,
            index.vectors, index.sqnorm, index.neighbors, s.visited)
        # Positional min over the shard dim: each neighbor slot j is
        # finite on its single owner shard, so this restores the exact
        # [B, M] layout (and top_k tie order) of the unsharded step.
        dist = dist_all.reshape(b, nshards, mdeg).min(axis=1)
        topk = (local_frontier_topk if pin_merge and bh is not None
                else hnsw_lib.frontier_topk)
        return hnsw_lib.merge_expand(s, cand_exp, act, nbrs, dist,
                                     visited, k=k, topk=topk)

    # Same jit discipline as the probe step: the index crosses the jit
    # boundary as an argument so its committed row sharding is respected.
    return _memoized(_BEAM_CACHE, key,
                     lambda: jax.jit(beam_step, static_argnames=("k",)))


__all__ = ["make_sharded_flat_search", "sharded_flat_search",
           "make_sharded_ivf_init", "make_sharded_probe_step",
           "make_sharded_beam_step", "merge_topk", "shard_count",
           "SHARD_AXIS", "BATCH_AXIS"]
