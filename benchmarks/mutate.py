"""Streaming-mutation benchmark: serve throughput + achieved recall
across an insert/delete burst, before and after recalibration and
compaction (the repro.mutate subsystem's end-to-end cost story).

Phases (all served through the slot-pool DarthServer at mixed declared
targets):
  pre-burst         frozen index, freshly fit predictor
  post-burst        +20% inserts (30% drifted/OOD), -10% deletes; the
                    predictor is still the frozen-index fit
  post-recalibrate  drift monitor refit + hot-swap
  post-compact      delta folded into the base, empty ring

Each phase reports host-side qps, mean achieved recall per declared
target against FRESH ground truth over the live base+delta set, and the
mean distance count.
"""
from __future__ import annotations

import time

import numpy as np
import jax.numpy as jnp

from repro import mutate
from repro.core import api, engines
from repro.data import vectors
from repro.index import flat, ivf
from repro.serve import DarthServer

K = 10
TARGETS = (0.8, 0.9, 0.95)


def mutate_burst(n: int = 20_000, d: int = 32, queries: int = 384):
    ds = vectors.make_dataset(n=n, d=d, num_learn=2_000,
                              num_queries=queries, clusters=128,
                              cluster_std=1.3, seed=0)
    index = ivf.build(ds.base, nlist=128, seed=0)
    mut = mutate.MutableIndex(
        index, capacity=-(-int(0.2 * n) // 128) * 128)

    def make_engine(**kw):
        return engines.mutable_engine(
            engines.ivf_engine(mut.base, **kw), mut.delta)

    darth = api.Darth(make_engine=make_engine,
                      engine=make_engine(k=K, nprobe=128))
    darth.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base))

    rng = np.random.default_rng(0)
    r_targets = rng.choice(TARGETS, size=queries).astype(np.float32)
    server = DarthServer(darth.engine, darth.trained.predictor,
                         darth.interval_for_target, num_slots=64)
    monitor = mutate.RecalibrationMonitor(mut, darth, targets=TARGETS,
                                          threshold=0.01)

    rows = []

    def live_gt():
        """Exact live ground truth — memoized on the mutation epoch by
        MutableIndex itself (post-burst and post-recalibrate share one
        live set, so they share one scan)."""
        return mut.live_ground_truth(ds.queries, K)

    def phase(label):
        t0 = time.time()
        results, stats = server.serve(ds.queries, r_targets)
        dt = time.time() - t0
        done = np.array([i for i, r in enumerate(results)
                         if r is not None])
        if done.size == 0:
            rows.append({"phase": label, "qps": 0.0,
                         "seconds": round(dt, 2), "error": "no results"})
            return rows[-1]
        ids = np.stack([results[i][1] for i in done])
        gt = live_gt()[done]
        rec = np.asarray(flat.recall_at_k(jnp.asarray(ids),
                                          jnp.asarray(gt)))
        monitor.observe(ds.queries[done], r_targets[done], ids)
        row = {"phase": label, "qps": round(len(done) / dt, 1),
               "seconds": round(dt, 2),
               "slot_steps": stats.slot_steps}
        for t in TARGETS:
            sel = r_targets[done] == np.float32(t)
            # null (not NaN) when a target drew no completed queries —
            # results/benchmarks.json must stay standard JSON
            row[f"recall@{t}"] = (round(float(rec[sel].mean()), 4)
                                  if sel.any() else None)
        rows.append(row)
        return row

    phase("pre-burst")

    events = vectors.mutation_stream(ds, insert_pct=0.2, delete_pct=0.1,
                                     drift=0.3, steps=6, seed=1)
    t0 = time.time()
    mut.apply(events)
    mutate_s = time.time() - t0
    server.set_engine(make_engine(k=K, nprobe=128),
                      contents_only=True)
    darth.engine = server.engine
    burst = phase("post-burst")

    rep = monitor.drift()
    t0 = time.time()
    monitor.recalibrate(ds.learn, server=server)
    recal_s = time.time() - t0
    phase("post-recalibrate")

    t0 = time.time()
    mut.compact()
    compact_s = time.time() - t0
    server.set_engine(make_engine(k=K, nprobe=128),
                      contents_only=True)
    darth.engine = server.engine
    final = phase("post-compact")

    rows.append({"phase": "costs", "mutate_seconds": round(mutate_s, 2),
                 "recalibrate_seconds": round(recal_s, 2),
                 "compact_seconds": round(compact_s, 2),
                 "drift_worst_gap": round(rep.worst_gap, 4),
                 "num_live": mut.num_live})
    if "recall@0.9" in burst and "recall@0.9" in final:
        headline = (f"post-burst r@.9 {burst['recall@0.9']:.3f} -> "
                    f"post-compact {final['recall@0.9']:.3f}; "
                    f"compact {compact_s:.1f}s")
    else:
        headline = f"phase returned no results; compact {compact_s:.1f}s"
    return rows, headline


def mutate_online_compaction(n: int = 8_000, d: int = 24,
                             queries: int = 320, slots: int = 8,
                             ticks_per_boundary: int = 1):
    """p99-during-compaction: serve a query stream while a mutation
    stream lands one event per chunk boundary, then fold the delta —
    three ways:

      baseline    mutations only, never compacts (the latency floor)
      background  incremental rebuild ticked at boundaries, atomic
                  hot-swap at a drained boundary (the tentpole path)
      sync        stop-the-world compact() inside one boundary (the
                  old behavior, kept as the spike to beat)

    Boundary-to-boundary wall times come from an on_boundary timestamp
    hook, so the host-side tick work IS inside the measured latency.
    Each interval is tagged with the action taken at its opening
    boundary; the gate compares the p99 of the DURING-COMPACTION window
    (begin + tick intervals) against the baseline's overall p99 — the
    swap boundary itself is reported separately as `swap_stall_ms`
    (its cost is the one-time XLA recompile for the grown base shapes,
    paid once at cutover, not per-chunk while rebuilding).

    Gates: background during-compaction p99 <= 1.5x baseline p99, all
    queries complete, and the post-swap base must be EXACTLY equal
    (arrays + served topk_d/topk_i/ndis at hosts {1, 2}) to a
    from-scratch synchronous rebuild."""
    import jax

    from repro import dist
    from repro.launch import mesh as mesh_lib

    ds = vectors.make_dataset(n=n, d=d, num_learn=1_000,
                              num_queries=queries, clusters=64,
                              cluster_std=1.3, seed=0)
    index = ivf.build(ds.base, nlist=64, seed=0)
    cap = -(-int(0.15 * n) // 128) * 128
    events = vectors.mutation_stream(ds, insert_pct=0.15, delete_pct=0.05,
                                     drift=0.3, steps=6, seed=1)

    mut0 = mutate.MutableIndex(index, capacity=cap)

    def make_engine(mut, **kw):
        return engines.mutable_engine(
            engines.ivf_engine(mut.base, **kw), mut.delta)

    darth = api.Darth(
        make_engine=lambda **kw: make_engine(mut0, **kw),
        engine=make_engine(mut0, k=K, nprobe=64))
    darth.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base))
    rng = np.random.default_rng(0)
    r_targets = rng.choice(TARGETS, size=queries).astype(np.float32)

    # Reference from-scratch rebuild FIRST: it is both the equality
    # oracle and the jit warmup for the compaction shapes, so the timed
    # background run measures tick work, not compile time.
    ref = mutate.MutableIndex(index, capacity=cap)
    ref.apply(events)
    ref.compact()

    rows = []

    def run_mode(mode: str):
        mut = mutate.MutableIndex(index, capacity=cap)
        server = DarthServer(make_engine(mut, k=K, nprobe=64),
                             darth.trained.predictor,
                             darth.interval_for_target, num_slots=slots)
        ev = list(events)
        stamps = []
        tags = []
        state = {"swapped": False}

        def on_boundary(srv):
            stamps.append(time.perf_counter())
            if srv.swap_pending or state["swapped"]:
                tags.append("drain" if srv.swap_pending else "idle")
                return
            if ev:
                tags.append("event")
                e = ev.pop(0)
                mut.apply([e])
                srv.set_engine(mutate.refresh_view(
                    srv.engine,
                    base=mut.base if e.kind == "delete" else None,
                    delta=mut.delta), contents_only=True)
            elif mode == "baseline":
                tags.append("idle")
            elif mode == "sync":
                tags.append("sync_compact")
                mut.compact()          # stop-the-world, inside a boundary
                srv.request_swap(make_engine(mut, k=K, nprobe=64),
                                 contents_only=True)
                state["swapped"] = True
            elif not mut.compacting:
                tags.append("begin")
                mut.begin_compaction()
            else:
                done = False
                for _ in range(ticks_per_boundary):
                    done = mut.compact_tick()
                    if done:
                        break
                if done:
                    tags.append("swap_req")
                    mut.swap_compaction()
                    srv.request_swap(make_engine(mut, k=K, nprobe=64),
                                     contents_only=True)
                    state["swapped"] = True
                else:
                    tags.append("tick")

        results, stats = server.serve(ds.queries, r_targets,
                                      on_boundary=on_boundary)
        # leftovers (short serve phase) drain off-clock — same
        # generator code path, so the folded base is identical
        if ev:
            mut.apply(ev)
            ev.clear()
        if mode != "baseline" and not state["swapped"]:
            if mut.compacting:
                while not mut.compact_tick():
                    pass
                mut.swap_compaction()
            else:
                mut.compact()
        deltas = np.diff(np.asarray(stamps)) * 1e3
        # interval i (stamps[i] -> stamps[i+1]) carries the cost of the
        # action taken at its OPENING boundary plus one chunk step
        by_tag = {}
        for t, ms in zip(tags[:-1], deltas):
            by_tag.setdefault(t, []).append(float(ms))
        window = by_tag.get("begin", []) + by_tag.get("tick", [])
        # the swap boundary: request + drain + the apply's one-time
        # recompile for the grown base shapes
        stall = (by_tag.get("swap_req", []) + by_tag.get("drain", [])
                 + by_tag.get("sync_compact", []))
        ndone = sum(1 for r in results if r is not None)
        rows.append({"mode": mode,
                     "boundaries": len(stamps),
                     "p50_ms": round(float(np.percentile(deltas, 50)), 2),
                     "p99_ms": round(float(np.percentile(deltas, 99)), 2),
                     "compaction_window_p99_ms":
                         (round(float(np.percentile(window, 99)), 2)
                          if window else None),
                     "swap_stall_ms": (round(max(stall), 2)
                                       if stall else None),
                     "swaps": stats.swaps,
                     "swapped_mid_serve": state["swapped"],
                     "completed": ndone})
        return mut, rows[-1]

    _, base_row = run_mode("baseline")
    mut_bg, bg_row = run_mode("background")
    _, sync_row = run_mode("sync")

    # -- gate 1: no stop-the-world pause in the background path --------
    p99_base = base_row["p99_ms"]
    p99_bg = bg_row["compaction_window_p99_ms"]
    if bg_row["completed"] != queries:
        raise RuntimeError(
            f"background mode completed {bg_row['completed']}/{queries}")
    if p99_bg is None:
        raise RuntimeError("background compaction never overlapped the "
                           "serve phase — no window to measure")
    if p99_bg > 1.5 * p99_base:
        raise RuntimeError(
            f"background compaction p99 {p99_bg:.1f}ms exceeds 1.5x "
            f"no-compaction baseline {p99_base:.1f}ms")

    # -- gate 2: post-swap base EXACTLY equals a from-scratch rebuild --
    for field in ("centroids", "bucket_vecs", "bucket_ids",
                  "bucket_sqnorm"):
        a = np.asarray(getattr(mut_bg.base, field))
        b = np.asarray(getattr(ref.base, field))
        if not np.array_equal(a, b):
            raise RuntimeError(f"post-swap base.{field} differs from "
                               f"the from-scratch rebuild")

    # -- gate 3: served results identical at hosts {1, 2}, through the
    # sharded multi-host mesh when the device pool allows it ----------
    def parity_serve(mut, hosts):
        mesh = (mesh_lib.make_serve_mesh(hosts, 2)
                if jax.device_count() >= hosts * 2 else None)
        if mesh is not None:
            view = dist.place_index(mut.view(), mesh)
            eng = engines.mutable_engine(
                engines.sharded_ivf_engine(view.base, mesh,
                                           k=K, nprobe=64), view.delta)
        else:
            eng = make_engine(mut, k=K, nprobe=64)
        srv = DarthServer(eng, darth.trained.predictor,
                          darth.interval_for_target, num_slots=slots,
                          mesh=mesh, hosts=hosts)
        results, stats = srv.serve(ds.queries, r_targets)
        return results, stats, mesh is not None

    parity = {}
    for h in (1, 2):
        res_bg, st_bg, meshed = parity_serve(mut_bg, h)
        res_ref, st_ref, _ = parity_serve(ref, h)
        for qi, (a, b) in enumerate(zip(res_bg, res_ref)):
            if (a is None) != (b is None):
                raise RuntimeError(f"hosts={h} q{qi}: completion differs")
            if a is not None and not (np.array_equal(a[0], b[0])
                                      and np.array_equal(a[1], b[1])):
                raise RuntimeError(f"hosts={h} q{qi}: topk differs "
                                   f"between swapped and rebuilt index")
        if st_bg.ndis_harvested != st_ref.ndis_harvested:
            raise RuntimeError(
                f"hosts={h}: ndis {st_bg.ndis_harvested} != "
                f"{st_ref.ndis_harvested}")
        parity[h] = {"ndis": st_bg.ndis_harvested, "sharded": meshed}
    rows.append({"mode": "parity", "hosts": {str(h): v for h, v
                                             in parity.items()},
                 "base_fields_equal": True})

    stall_bg = bg_row["swap_stall_ms"] or 0.0
    stall_sync = sync_row["swap_stall_ms"] or 0.0
    headline = (f"compacting p99 {p99_bg:.0f}ms vs base {p99_base:.0f}ms"
                f"; cutover stall bg {stall_bg:.0f}ms vs sync "
                f"{stall_sync:.0f}ms; {bg_row['swaps']} swap(s); "
                f"parity@hosts{{1,2}} ok")
    return rows, headline


if __name__ == "__main__":
    from benchmarks.artifact import write_bench_artifact
    from repro.launch import compile_cache
    compile_cache.enable()
    out = {}
    for fn in (mutate_burst, mutate_online_compaction):
        rows, headline = fn()
        for r in rows:
            print(r)
        print(headline)
        out[fn.__name__] = {"headline": headline, "rows": rows}
    print("wrote", write_bench_artifact(out))
