"""Uniform engine adapters: one step-wise protocol over IVF and HNSW.

DARTH's driver (darth_search.py) is engine-agnostic: anything that exposes
init/step plus the counters the features need can be driven to a declarative
recall target. This mirrors the paper's claim (§3.3) that Algorithm 1
generalizes across ANNS methods whose search is iterative.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax

from repro.index import hnsw as hnsw_lib
from repro.index import ivf as ivf_lib


class Engine(NamedTuple):
    """Step-wise search engine protocol.

    All state objects must carry: .active bool[B], .ndis i32[B],
    .ninserts i32[B], .first_nn f32[B].

    init/step take the index EXPLICITLY (drivers call
    `engine.init(engine.index, q)` / `engine.step(engine.index, s)`),
    never through the closure: a sharded index closure-captured inside
    an outer jit (e.g. the slot-pool server's chunk functions) would be
    baked in as a fully REPLICATED constant, silently undoing
    dist.place_index. Passing it as an argument keeps its committed
    sharding on every jit path.
    """
    index: Any
    init: Callable[[Any, jax.Array], Any]
    step: Callable[[Any, Any], Any]
    topk_d: Callable[[Any], jax.Array]   # f32[B, K] squared, ascending
    topk_i: Callable[[Any], jax.Array]   # i32[B, K]
    nstep: Callable[[Any], jax.Array]    # i32[B]
    max_steps: int
    name: str
    k: int


def set_active(state: Any, mask: jax.Array) -> Any:
    return dataclasses.replace(state, active=mask)


def ivf_engine(index: ivf_lib.IVFIndex, *, k: int, nprobe: int) -> Engine:
    return Engine(
        index=index,
        init=lambda idx, q: ivf_lib.init_state(idx, q, k=k, nprobe=nprobe),
        step=ivf_lib.probe_step,
        topk_d=lambda s: s.topk_d,
        topk_i=lambda s: s.topk_i,
        nstep=lambda s: s.probe_pos,
        max_steps=nprobe,
        name="ivf",
        k=k,
    )


def sharded_ivf_engine(index: ivf_lib.IVFIndex, mesh, *, k: int, nprobe: int,
                       use_kernel: bool = True,
                       interpret: Optional[bool] = None,
                       pin_merge: bool = True) -> Engine:
    """ShardedIVFEngine: the IVF probe loop over a cap-sharded bucket
    store (dist.place_index + dist.collectives.make_sharded_probe_step).

    Same Engine protocol and the same IVFSearchState as ivf_engine, so
    darth_search / budget_search / the slot-pool server drive it
    unchanged; only the probe step's data movement differs (per-shard
    bucket_topk + one [B, k] all-gather merge instead of a GSPMD bucket
    gather). `index` must have been placed with dist.place_index(index,
    mesh) so its bucket cap divides the shard count."""
    from repro.dist import collectives as dist_collectives

    # make_sharded_probe_step returns a jitted step(index, state): the
    # index goes through every jit boundary as an argument so its
    # committed cap-axis sharding is respected (a closure const would
    # replicate — see the Engine docstring).
    # pin_merge keeps the candidate top-k merge inside the shard_map so
    # a hosts-split slot dim never feeds the unpartitionable TopK
    # custom-call (see make_sharded_probe_step); False is the pre-fix
    # behavior, kept for collective-traffic benchmarking.
    step = dist_collectives.make_sharded_probe_step(
        mesh, use_kernel=use_kernel, interpret=interpret,
        pin_merge=pin_merge)
    # The init's centroid-ranking top_k is pinned the same way (plain
    # ivf.init_state inside the server's init chunk would all-gather
    # the hosts-split slot rows to feed the TopK custom-call).
    init = dist_collectives.make_sharded_ivf_init(mesh)
    return Engine(
        index=index,
        init=lambda idx, q: init(idx, q, k=k, nprobe=nprobe),
        step=step,
        topk_d=lambda s: s.topk_d,
        topk_i=lambda s: s.topk_i,
        nstep=lambda s: s.probe_pos,
        max_steps=nprobe,
        name="ivf-sharded",
        k=k,
    )


def hnsw_engine(index: hnsw_lib.HNSWIndex, *, k: int, ef: int,
                max_steps: int = 0, visited_width: int = 0) -> Engine:
    """`visited_width` > 0 swaps the exact [B, N] visited bitmap for a
    fixed-width hashed filter [B, visited_width] (power of two < N; see
    hnsw.init_state) so the per-query state stops scaling with N."""
    limit = max_steps or 8 * ef
    return Engine(
        index=index,
        init=lambda idx, q: hnsw_lib.init_state(
            idx, q, ef=ef, visited_width=visited_width),
        step=lambda idx, s: hnsw_lib.beam_step(idx, s, k=k),
        topk_d=lambda s: s.cand_d[:, :k],
        topk_i=lambda s: s.cand_i[:, :k],
        nstep=lambda s: s.nstep,
        max_steps=limit,
        name="hnsw",
        k=k,
    )


def mutable_engine(base_engine: Engine, delta, *,
                   interpret: Optional[bool] = None) -> Engine:
    """MutableEngine: wrap ANY engine (single-device or sharded) with a
    delta tier — init adds one brute-force delta scan (fused l2_topk),
    step is the base probe/beam step, and the top-k getters merge the
    delta candidates via merge_topk. Tombstoned slots carry sqnorm +inf
    / ids -1 (the shard-pad convention) in base and delta alike, so
    deletes are invisible to every driver. See repro.mutate."""
    from repro.mutate import engine as mutate_engine_lib

    return mutate_engine_lib.mutable_engine(base_engine, delta,
                                            interpret=interpret)


def sharded_hnsw_engine(index: hnsw_lib.HNSWIndex, mesh, *, k: int, ef: int,
                        max_steps: int = 0, pin_merge: bool = True,
                        visited_width: int = 0) -> Engine:
    """ShardedHNSWEngine: the beam loop over a row-sharded graph
    (dist.place_index + dist.collectives.make_sharded_beam_step).

    Same Engine protocol and the same HNSWSearchState as hnsw_engine, so
    darth_search / budget_search / the slot-pool server drive it
    unchanged; only the beam step's data movement differs (per-shard
    neighbor resolution + one [B, M] psum/all-gather frontier merge
    instead of a GSPMD gather of neighbor lists and vectors). `index`
    must have been placed with dist.place_index(index, mesh) so its node
    count divides the shard count. `visited_width` > 0 selects the
    hashed visited filter (must also divide the shard count — the
    filter splits over "model" inside the step)."""
    from repro.dist import collectives as dist_collectives

    # make_sharded_beam_step returns a jitted step(index, state, k=..):
    # the index goes through every jit boundary as an argument so its
    # committed row sharding is respected (a closure const would
    # replicate — see the Engine docstring).
    # pin_merge: frontier top-k runs inside the shard_map (the TopK
    # custom-call cannot be partitioned over a hosts-split slot dim —
    # see make_sharded_beam_step); False is the pre-fix behavior.
    step = dist_collectives.make_sharded_beam_step(mesh,
                                                   pin_merge=pin_merge)
    limit = max_steps or 8 * ef
    return Engine(
        index=index,
        init=lambda idx, q: hnsw_lib.init_state(
            idx, q, ef=ef, visited_width=visited_width),
        step=lambda idx, s: step(idx, s, k=k),
        topk_d=lambda s: s.cand_d[:, :k],
        topk_i=lambda s: s.cand_i[:, :k],
        nstep=lambda s: s.nstep,
        max_steps=limit,
        name="hnsw-sharded",
        k=k,
    )
