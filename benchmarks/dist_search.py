"""Sharded flat-search benchmark: wall time + HLO collective-traffic
accounting (utils/hlo.collective_bytes) for the cross-shard top-k merge.

Run standalone with forced placeholder devices to see real shard counts:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.dist_search
"""
from __future__ import annotations

import time

import numpy as np


def dist_sharded_search(n: int = 20_000, d: int = 32, b: int = 256,
                        k: int = 10):
    import jax.numpy as jnp

    from repro import dist
    from repro.index import flat
    from repro.launch import mesh as mesh_lib
    from repro.utils import hlo as hlo_lib

    mesh = mesh_lib.make_search_mesh()
    shards = dist.collectives.shard_count(mesh)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)

    fn = dist.make_sharded_flat_search(mesh, k)
    compiled = fn.lower(q, x).compile()  # single compile serves run + HLO
    coll = hlo_lib.collective_bytes(compiled.as_text())

    d_sh, i_sh = compiled(q, x)
    d_sh.block_until_ready()
    t0 = time.time()
    reps = 5
    for _ in range(reps):
        d_sh, i_sh = compiled(q, x)
    d_sh.block_until_ready()
    us_per_batch = (time.time() - t0) / reps * 1e6

    d_ref, i_ref = flat.search(q, x, k)
    err = float(np.max(np.abs(np.asarray(d_sh) - np.asarray(d_ref))))
    recall = float(np.mean(np.asarray(
        flat.recall_at_k(i_sh, i_ref))))

    rows = [{
        "shards": shards, "n": n, "batch": b, "k": k,
        "collective_bytes_per_batch": coll["total"],
        "collective_ops": coll["num_ops"],
        "us_per_batch": round(us_per_batch),
        "max_abs_err_vs_flat": err, "recall_vs_flat": recall,
    }]
    headline = (f"{shards} shard(s): {coll['total']/1e3:.1f} kB "
                f"collectives/batch, err {err:.2e}, recall {recall:.4f}")
    return rows, headline


def dist_sharded_ivf_probe(n: int = 20_000, d: int = 32, b: int = 64,
                           k: int = 10, nlist: int = 64, nprobe: int = 8):
    """Sharded IVF probe: collective traffic of the shard_map fast path
    (per-shard bucket_topk + [B, k] all-gather merge) vs driving the
    plain probe_step over the same cap-sharded index through GSPMD
    gathers, plus numeric parity against single-device ivf.search."""
    import jax
    import jax.numpy as jnp

    from repro import dist
    from repro.index import flat, ivf
    from repro.launch import mesh as mesh_lib
    from repro.utils import hlo as hlo_lib

    mesh = mesh_lib.make_search_mesh()
    shards = dist.collectives.shard_count(mesh)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)

    index = ivf.build(x, nlist=nlist, seed=0)
    placed = dist.place_index(index, mesh)

    # Both steps are jitted taking the index as an explicit argument:
    # closure-captured consts lose their committed shardings, which
    # would hide the GSPMD traffic (and replicate the bucket store).
    step = dist.collectives.make_sharded_probe_step(mesh)
    s0 = ivf.init_state(placed, q, k=k, nprobe=nprobe)
    fast_c = step.lower(placed, s0).compile()
    coll_fast = hlo_lib.collective_bytes(fast_c.as_text())
    coll_gspmd = hlo_lib.collective_bytes(
        ivf.probe_step.lower(placed, s0).compile().as_text())

    s = fast_c(placed, s0)
    s.topk_d.block_until_ready()
    t0 = time.time()
    reps = 5
    for _ in range(reps):
        s = fast_c(placed, s0)
    s.topk_d.block_until_ready()
    us_per_step = (time.time() - t0) / reps * 1e6

    d_sh, i_sh, _ = ivf.search_sharded(placed, q, k=k, nprobe=nprobe,
                                       mesh=mesh)
    d_ref, i_ref, _ = ivf.search(index, q, k=k, nprobe=nprobe)
    ids_eq = bool(np.array_equal(np.asarray(i_sh), np.asarray(i_ref)))
    recall = float(np.mean(np.asarray(flat.recall_at_k(i_sh, i_ref))))

    rows = [{
        "shards": shards, "n": n, "batch": b, "k": k,
        "nlist": nlist, "nprobe": nprobe, "cap": placed.cap,
        "collective_bytes_fast_path": coll_fast["total"],
        "collective_bytes_gspmd_gather": coll_gspmd["total"],
        "us_per_probe_step": round(us_per_step),
        "ids_match_single_device": ids_eq, "recall_vs_single": recall,
    }]
    headline = (f"{shards} shard(s): {coll_fast['total']/1e3:.1f} kB/probe "
                f"shard_map vs {coll_gspmd['total']/1e3:.1f} kB GSPMD, "
                f"ids_eq {ids_eq}")
    return rows, headline


def dist_sharded_hnsw_beam(b: int = 32, k: int = 10, m: int = 8,
                           ef: int = 48):
    """Sharded HNSW beam step: collective traffic of the shard_map fast
    path (per-shard neighbor resolution + [B, M] psum/all-gather
    frontier merge) vs driving the plain beam_step over the same
    row-sharded graph through GSPMD gathers, plus numeric parity against
    single-device hnsw.search. Two (N, D) sizes pin the fast path's
    per-step bytes as independent of N and D (O(B*M*shards)), while the
    GSPMD gather baseline scales with D."""
    import jax.numpy as jnp

    from repro import dist
    from repro.index import hnsw
    from repro.launch import mesh as mesh_lib
    from repro.utils import hlo as hlo_lib

    mesh = mesh_lib.make_search_mesh()
    shards = dist.collectives.shard_count(mesh)
    step = dist.collectives.make_sharded_beam_step(mesh)
    rng = np.random.default_rng(0)

    rows = []
    for n, d in ((4000, 16), (8000, 32)):
        x = rng.normal(size=(n, d)).astype(np.float32)
        q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
        index = hnsw.build(x, m=m, passes=1, ef_construction=32, seed=0)
        placed = dist.place_index(index, mesh)

        s0 = hnsw.init_state(placed, q, ef=ef)
        fast_c = step.lower(placed, s0, k=k).compile()
        coll_fast = hlo_lib.collective_bytes(fast_c.as_text())
        coll_gspmd = hlo_lib.collective_bytes(
            hnsw.beam_step.lower(placed, s0, k=k).compile().as_text())

        s = fast_c(placed, s0)
        s.cand_d.block_until_ready()
        t0 = time.time()
        reps = 5
        for _ in range(reps):
            s = fast_c(placed, s0)
        s.cand_d.block_until_ready()
        us_per_step = (time.time() - t0) / reps * 1e6

        d_sh, i_sh, s_sh = hnsw.search_sharded(placed, q, k=k, ef=ef,
                                               mesh=mesh)
        d_ref, i_ref, s_ref = hnsw.search(index, q, k=k, ef=ef)
        rows.append({
            "shards": shards, "n": n, "d": d, "batch": b, "k": k, "m": m,
            "ef": ef, "n_padded": placed.num_vectors,
            "collective_bytes_fast_path": coll_fast["total"],
            "collective_bytes_gspmd_gather": coll_gspmd["total"],
            "us_per_beam_step": round(us_per_step),
            "ids_match_single_device": bool(np.array_equal(
                np.asarray(i_sh), np.asarray(i_ref))),
            "ndis_match_single_device": bool(np.array_equal(
                np.asarray(s_sh.ndis), np.asarray(s_ref.ndis))),
        })

    size_free = rows[0]["collective_bytes_fast_path"] == \
        rows[-1]["collective_bytes_fast_path"]
    headline = (f"{shards} shard(s): "
                f"{rows[-1]['collective_bytes_fast_path']/1e3:.1f} kB/step "
                f"shard_map (N/D-independent: {size_free}) vs "
                f"{rows[-1]['collective_bytes_gspmd_gather']/1e3:.1f} kB "
                f"GSPMD, ids_eq "
                f"{all(r['ids_match_single_device'] for r in rows)}")
    return rows, headline


def dist_residency(b: int = 8, k: int = 10, nlist: int = 32,
                   nprobe: int = 8, m: int = 8, ef: int = 48,
                   visited_width: int = 512):
    """Compact-residency gates (PR 10): the SQ8-resident sharded step
    programs (IVF probe over int8 codes, HNSW beam over int8 codes +
    the fixed-width hashed visited filter) must move the SAME per-step
    collective bytes at N=2048 and N=8192 — candidates, never index
    rows — and the device-resident index bytes must drop >= 3.5x vs
    f32 for the IVF layout at D=64 (the serving dim class the budget
    is written for; the HNSW ratio is reported ungated because its
    f32 row carries the adjacency list both formats keep). Recall at
    the large size shows the quantization + hashed-filter cost the
    conformance tests bound."""
    import jax.numpy as jnp

    from repro import dist
    from repro.index import flat, hnsw, ivf, residency
    from repro.launch import mesh as mesh_lib
    from repro.utils import hlo as hlo_lib

    mesh = mesh_lib.make_search_mesh()
    shards = dist.collectives.shard_count(mesh)
    d = 64
    rng = np.random.default_rng(0)
    rows = []
    coll = {"ivf": {}, "hnsw": {}}
    ratios = {}
    for n in (2048, 8192):
        x = rng.normal(size=(n, d)).astype(np.float32)
        q = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)

        index_f = ivf.build(x, nlist=nlist, seed=0)
        index_q = residency.quantize_ivf(index_f)
        placed = dist.place_index(index_q, mesh)
        step = dist.collectives.make_sharded_probe_step(mesh)
        s0 = ivf.init_state(placed, q, k=k, nprobe=nprobe)
        coll["ivf"][n] = hlo_lib.collective_bytes(
            step.lower(placed, s0).compile().as_text())["total"]

        graph_f = hnsw.build(x, m=m, passes=1, ef_construction=32,
                             seed=0)
        graph_q = residency.quantize_hnsw(graph_f)
        gplaced = dist.place_index(graph_q, mesh)
        bstep = dist.collectives.make_sharded_beam_step(mesh)
        gs0 = hnsw.init_state(gplaced, q, ef=ef,
                              visited_width=visited_width)
        coll["hnsw"][n] = hlo_lib.collective_bytes(
            bstep.lower(gplaced, gs0, k=k).compile().as_text())["total"]

        ratios[n] = {
            "ivf": (residency.resident_bytes(index_f)["total"]
                    / residency.resident_bytes(index_q)["total"]),
            "hnsw": (residency.resident_bytes(graph_f)["total"]
                     / residency.resident_bytes(graph_q)["total"]),
        }

        _, gt_i = flat.search(q, jnp.asarray(x), k)

        def recall(i_pred):
            return float(np.mean(np.asarray(
                flat.recall_at_k(i_pred, gt_i))))

        _, i_f32, _ = ivf.search(index_f, q, k=k, nprobe=nprobe)
        _, i_sq8, _ = ivf.search(index_q, q, k=k, nprobe=nprobe)
        _, gi_f32, _ = hnsw.search(graph_f, q, k=k, ef=ef)
        _, gi_sq8, _ = hnsw.search(graph_q, q, k=k, ef=ef,
                                   visited_width=visited_width)
        rows.append({
            "shards": shards, "n": n, "d": d, "k": k,
            "nlist": nlist, "nprobe": nprobe, "m": m, "ef": ef,
            "visited_width": visited_width,
            "ivf_collective_bytes_per_step": coll["ivf"][n],
            "hnsw_collective_bytes_per_step": coll["hnsw"][n],
            "ivf_resident_ratio_f32_over_sq8": round(ratios[n]["ivf"], 3),
            "hnsw_resident_ratio_f32_over_sq8": round(
                ratios[n]["hnsw"], 3),
            "ivf_recall_f32": round(recall(i_f32), 4),
            "ivf_recall_sq8": round(recall(i_sq8), 4),
            "hnsw_recall_f32": round(recall(gi_f32), 4),
            "hnsw_recall_sq8_hashed": round(recall(gi_sq8), 4),
        })

    n_indep = (coll["ivf"][2048] == coll["ivf"][8192]
               and coll["hnsw"][2048] == coll["hnsw"][8192])
    ratio_ok = ratios[8192]["ivf"] >= 3.5
    rows[-1]["gate_collective_bytes_n_independent"] = n_indep
    rows[-1]["gate_ivf_resident_ratio_ge_3_5"] = ratio_ok
    headline = (f"{shards} shard(s): SQ8 steps "
                f"{coll['ivf'][8192]/1e3:.1f} kB ivf / "
                f"{coll['hnsw'][8192]/1e3:.1f} kB hnsw per step, "
                f"N-independent {'PASS' if n_indep else 'FAIL'}; "
                f"resident f32/sq8 {ratios[8192]['ivf']:.2f}x ivf "
                f"(gate>=3.5x {'PASS' if ratio_ok else 'FAIL'}), "
                f"{ratios[8192]['hnsw']:.2f}x hnsw")
    return rows, headline


def dist_multi_host_serve(n: int = 20_000, d: int = 32, k: int = 10,
                          nlist: int = 64, nprobe: int = 16,
                          slots: int = 64, steps_per_sync: int = 4,
                          stream: int = 128):
    """Multi-host slot-pool serve traffic: per-chunk collective bytes of
    the jitted run_chunk on a ("hosts", "model") serve mesh (slot dim
    split over host groups, index global per group) vs the
    single-controller server on a ("model",)-only mesh. With the
    candidate merges pinned inside the shard_map (pin_merge — the TopK
    custom-call cannot be partitioned, so an outside merge forced a
    cross-host all-gather of its operands) the slot split makes every
    per-chunk collective host-group-local, so multi-host bytes must
    come in BELOW single-controller: the nightly gate asserts the ratio
    < 1.05x (gate_pass). A short serve stream sanity-checks that the
    per-host loops actually drain their stripes."""
    import jax
    import jax.numpy as jnp

    from repro import dist
    from repro.core import engines
    from repro.core.intervals import IntervalParams
    from repro.index import ivf
    from repro.launch import mesh as mesh_lib
    from repro.serve import DarthServer
    from repro.utils import hlo as hlo_lib

    ndev = jax.device_count()
    hosts = 2 if ndev >= 8 else 1
    shards = 4 if ndev >= 8 else max(ndev // max(hosts, 1), 1)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    index = ivf.build(x, nlist=nlist, seed=0)

    # Predictor/interval stubs: the chunk's collective traffic does not
    # depend on trained values, only on shapes and the engine step.
    def predictor(feats):
        return jnp.full((feats.shape[0],), 0.5, jnp.float32)

    def interval_for_target(rt):
        rt = np.atleast_1d(rt)
        return IntervalParams(ipi=np.full(rt.shape, 64.0, np.float32),
                              mpi=np.full(rt.shape, 8.0, np.float32))

    def measure(mesh, host_loops, label):
        placed = dist.place_index(index, mesh)
        eng = engines.sharded_ivf_engine(placed, mesh, k=k, nprobe=nprobe)
        server = DarthServer(eng, predictor, interval_for_target,
                             num_slots=slots,
                             steps_per_sync=steps_per_sync,
                             mesh=mesh, hosts=host_loops)
        qb = rng.normal(size=(slots, d)).astype(np.float32)
        rt = np.full((slots,), 0.9, np.float32)
        ipi = np.full((slots,), 64.0, np.float32)
        mpi = np.full((slots,), 8.0, np.float32)
        st = server._init_chunk(eng.index, server._put(qb),
                                server._put(ipi), server._put(mpi))
        compiled = server._run_chunk.lower(
            eng.index, st, server._put(rt), server._put(ipi),
            server._put(mpi)).compile()
        coll = hlo_lib.collective_bytes(compiled.as_text())

        q = rng.normal(size=(stream, d)).astype(np.float32)
        t0 = time.time()
        results, stats = server.serve(q, np.full((stream,), 0.9,
                                                 np.float32))
        dt = time.time() - t0
        assert stats.completed == stream
        return {
            "topology": label, "hosts": host_loops,
            "shards": int(mesh.shape["model"]), "slots": slots,
            "steps_per_sync": steps_per_sync,
            "collective_bytes_per_chunk": coll["total"],
            "collective_ops_per_chunk": coll["num_ops"],
            "stream_qps": round(stream / max(dt, 1e-9), 1),
            "per_host_completed": [h.completed for h in stats.hosts],
        }

    rows = [
        measure(mesh_lib.make_search_mesh(shards), 1,
                "single-controller"),
        measure(mesh_lib.make_serve_mesh(hosts, shards), hosts,
                "multi-host"),
    ]
    sc, mh = rows[0], rows[1]
    ratio = (mh["collective_bytes_per_chunk"]
             / max(sc["collective_bytes_per_chunk"], 1))
    # the gate only means something on a genuinely multi-host mesh
    gate_pass = ratio < 1.05 if hosts > 1 else None
    mh["collective_bytes_ratio_vs_single"] = round(ratio, 4)
    mh["gate_ratio_below_1_05"] = gate_pass
    headline = (f"{hosts} host(s) x {shards} shard(s): "
                f"{mh['collective_bytes_per_chunk']/1e3:.1f} kB/chunk "
                f"multi-host vs "
                f"{sc['collective_bytes_per_chunk']/1e3:.1f} kB "
                f"single-controller ({ratio:.2f}x"
                + (f", gate<1.05x {'PASS' if gate_pass else 'FAIL'}"
                   if gate_pass is not None else "")
                + ")")
    return rows, headline


def dist_difficulty_serve(n: int = 20_000, d: int = 32, k: int = 10,
                          nlist: int = 64, nprobe: int = 16,
                          slots: int = 64, steps_per_sync: int = 4,
                          stream: int = 192):
    """Difficulty-aware multi-host serving: per-tier p99 recall/latency
    SLOs (serve.difficulty) through the slot-pool server on the serve
    mesh, plus per-chunk collective bytes with the merge-pinning fix on
    vs off (pin_merge True/False — the pre-fix chunk all-gathered merge
    operands across hosts because the TopK custom-call cannot be
    partitioned). A real DARTH fit drives termination so the reported
    recall percentiles are the predictor's actual harvest estimates."""
    import jax
    import jax.numpy as jnp

    from repro import dist
    from repro.core import api, engines
    from repro.index import flat, ivf
    from repro.launch import mesh as mesh_lib
    from repro.serve import DarthServer, TierConfig
    from repro.utils import hlo as hlo_lib

    ndev = jax.device_count()
    hosts = 2 if ndev >= 8 else 1
    shards = 4 if ndev >= 8 else max(ndev // max(hosts, 1), 1)
    mesh = (mesh_lib.make_serve_mesh(hosts, shards) if hosts > 1
            else mesh_lib.make_search_mesh(shards))

    from repro.data import vectors
    ds = vectors.make_dataset(n=n, d=d, num_learn=1024, num_queries=stream,
                              clusters=nlist, seed=0)
    index = ivf.build(ds.base, nlist=nlist, seed=0)
    placed = dist.place_index(index, mesh)

    def build_engine(**kw):
        return engines.sharded_ivf_engine(placed, mesh, **kw)

    darth = api.Darth(make_engine=build_engine,
                      engine=build_engine(k=k, nprobe=nprobe))
    darth.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base), mesh=mesh)

    rng = np.random.default_rng(1)
    r_targets = rng.choice([0.8, 0.9, 0.95],
                           size=stream).astype(np.float32)
    tiers = TierConfig(hard_quantile=0.75, hard_slot_fraction=0.25,
                       boost=0.05, hedge=True, rebalance=True)
    server = DarthServer(darth.engine, darth.trained.predictor,
                         darth.interval_for_target, num_slots=slots,
                         steps_per_sync=steps_per_sync, mesh=mesh,
                         hosts=hosts, tiers=tiers)
    t0 = time.time()
    results, stats = server.serve(ds.queries, r_targets)
    dt = time.time() - t0
    assert all(r is not None for r in results)

    # ground-truth recall per tier (the TierStats percentiles are the
    # predictor's estimates; this is the real thing)
    _, gt_i = flat.search(jnp.asarray(ds.queries), jnp.asarray(ds.base), k)
    ids = np.stack([r[1] for r in results])
    rec = np.asarray(flat.recall_at_k(jnp.asarray(ids), gt_i))
    from repro.serve import difficulty as difficulty_lib
    is_hard = difficulty_lib.assign_tiers(
        difficulty_lib.difficulty_scores(darth.engine.index, ds.queries),
        tiers)

    # before/after collective bytes of the chunk program
    def chunk_bytes(pin):
        eng = build_engine(k=k, nprobe=nprobe, pin_merge=pin)
        srv = DarthServer(eng, darth.trained.predictor,
                          darth.interval_for_target, num_slots=slots,
                          steps_per_sync=steps_per_sync, mesh=mesh,
                          hosts=hosts)
        qb = rng.normal(size=(slots, d)).astype(np.float32)
        ipi = np.full((slots,), 64.0, np.float32)
        mpi = np.full((slots,), 8.0, np.float32)
        st = srv._init_chunk(eng.index, srv._put(qb), srv._put(ipi),
                             srv._put(mpi))
        rt = np.full((slots,), 0.9, np.float32)
        compiled = srv._run_chunk.lower(
            eng.index, st, srv._put(rt), srv._put(ipi),
            srv._put(mpi)).compile()
        return hlo_lib.collective_bytes(compiled.as_text())["total"]

    bytes_fixed = chunk_bytes(True)
    bytes_prefix = chunk_bytes(False)

    rows = []
    for tier, hard in (("easy", False), ("hard", True)):
        ts = stats.tiers[tier]
        sel = is_hard == hard
        rows.append({
            "topology": f"{hosts}x{shards}", "tier": tier,
            "queries": ts.count,
            "recall_p50_pred": round(ts.recall_p50, 4),
            "recall_p99_pred": round(ts.recall_p99, 4),
            "recall_p50_true": round(float(np.percentile(rec[sel], 50)), 4),
            "recall_p99_true": round(float(np.percentile(rec[sel], 1)), 4),
            "latency_p50_steps": ts.latency_p50,
            "latency_p99_steps": ts.latency_p99,
            "hedged": ts.hedged, "hedge_upgrades": ts.hedge_upgrades,
            "chunk_bytes_pinned_merge": bytes_fixed,
            "chunk_bytes_unpinned_merge": bytes_prefix,
            "chunk_ms_p50": round(stats.chunk_ms_p50, 2),
            "chunk_ms_p99": round(stats.chunk_ms_p99, 2),
            "stream_qps": round(stream / max(dt, 1e-9), 1),
        })
    hard_row = rows[1]
    headline = (f"{hosts} host(s) x {shards} shard(s): hard-tier p99 "
                f"recall {hard_row['recall_p99_true']:.3f} (true) / "
                f"{hard_row['recall_p99_pred']:.3f} (pred), latency p99 "
                f"{hard_row['latency_p99_steps']:.0f} steps; chunk "
                f"{bytes_fixed/1e3:.1f} kB pinned vs "
                f"{bytes_prefix/1e3:.1f} kB unpinned merge")
    return rows, headline


if __name__ == "__main__":
    from benchmarks.artifact import write_bench_artifact
    from repro.launch import compile_cache
    compile_cache.enable()
    out = {}
    for fn in (dist_sharded_search, dist_sharded_ivf_probe,
               dist_sharded_hnsw_beam, dist_residency,
               dist_multi_host_serve, dist_difficulty_serve):
        rows, headline = fn()
        print(headline)
        for r in rows:
            print(r)
        out[fn.__name__] = {"headline": headline, "rows": rows}
    print("wrote", write_bench_artifact(out))
