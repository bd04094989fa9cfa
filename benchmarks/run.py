"""Benchmark harness (deliverable d): one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes results/benchmarks.json
plus a per-commit results/BENCH_<utc-timestamp>.json artifact (same
payload + git metadata incl. a dirty flag, via benchmarks.artifact) on
EVERY invocation — nightly and local runs alike — so the perf series
accumulates one comparable point per run.
"""
from __future__ import annotations

import json
import os
import time
import traceback

from benchmarks.artifact import write_bench_artifact


def main() -> None:
    from benchmarks import dist_search, mutate, obs, paper_tables as pt
    from repro.launch import compile_cache

    compile_cache.enable()

    benches = [
        ("obs_tracing_overhead", obs.obs_tracing_overhead),
        ("dist_sharded_search", dist_search.dist_sharded_search),
        ("dist_sharded_ivf_probe", dist_search.dist_sharded_ivf_probe),
        ("dist_sharded_hnsw_beam", dist_search.dist_sharded_hnsw_beam),
        ("dist_residency", dist_search.dist_residency),
        ("dist_multi_host_serve", dist_search.dist_multi_host_serve),
        ("dist_difficulty_serve", dist_search.dist_difficulty_serve),
        ("mutate_burst", mutate.mutate_burst),
        ("mutate_online_compaction", mutate.mutate_online_compaction),
        ("table5_predictor_quality", pt.table5_predictor_quality),
        ("table4_training_cost", pt.table4_training_cost),
        ("fig5_interval_ablation", pt.fig5_interval_ablation),
        ("fig6_speedups_hnsw", lambda: pt.fig6_darth_speedups("hnsw")),
        ("fig19_speedups_ivf", lambda: pt.fig6_darth_speedups("ivf")),
        ("fig8_optimality_ivf", lambda: pt.fig8_optimality("ivf")),
        ("fig10_competitors", pt.fig10_competitors),
        ("fig11_hardness", pt.fig11_hardness),
        ("fig18_ood", pt.fig18_ood),
        ("feature_ablation", pt.feature_ablation),
        ("model_selection", pt.model_selection),
        ("serving_compaction", pt.serving_compaction),
    ]

    all_out = {}
    print("name,us_per_call,derived")
    for name, fn in benches:
        t0 = time.time()
        try:
            rows, headline = fn()
            status = "ok"
        except Exception as e:  # pragma: no cover
            rows, headline = [], f"ERROR {type(e).__name__}: {e}"
            status = "error"
            traceback.print_exc()
        dt = time.time() - t0
        us = dt * 1e6
        all_out[name] = {"status": status, "seconds": round(dt, 1),
                         "headline": headline, "rows": rows}
        print(f"{name},{us:.0f},{headline}", flush=True)

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.json", "w") as f:
        json.dump(all_out, f, indent=1, default=str)
    path = write_bench_artifact(all_out)
    print(f"wrote results/benchmarks.json + {path}")
    n_err = sum(1 for v in all_out.values() if v["status"] != "ok")
    if n_err:
        raise SystemExit(f"{n_err} benchmarks failed")


if __name__ == "__main__":
    main()
