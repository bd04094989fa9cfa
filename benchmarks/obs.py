"""Tracing-overhead benchmark: the repro.obs contract is that turning
tracing ON costs <= 5% p99 chunk latency (docs/observability.md).

The trajectory ring rides the existing chunk jits and is drained only
at the sync boundaries serve() already pays for, so the only added
work is one [slots, traj_cap] dynamic-index write per engine step plus
host-side span bookkeeping. This benchmark serves the SAME workload
through a traced and an untraced server, interleaved over several
repeats (so CPU frequency / page-cache drift hits both arms equally),
and gates on the ratio of the best-of-repeats p99 chunk wall times.

Run standalone (exits nonzero when the gate fails):
  PYTHONPATH=src python -m benchmarks.obs
"""
from __future__ import annotations

import numpy as np

#: the overhead contract: tracing-on p99 chunk latency <= 1.05x off
OVERHEAD_GATE = 1.05


def _build(tracer=None, metrics=None):
    import jax.numpy as jnp

    from repro.core import api, engines
    from repro.data import vectors
    from repro.index import ivf
    from repro.serve import DarthServer

    ds = vectors.make_dataset(n=8_000, d=16, num_learn=512,
                              num_queries=192, clusters=32, seed=7)
    index = ivf.build(ds.base, nlist=32, seed=7)
    eng = engines.ivf_engine(index, k=10, nprobe=32)
    darth = api.Darth(
        make_engine=lambda **kw: engines.ivf_engine(index, **kw),
        engine=eng)
    darth.fit(jnp.asarray(ds.learn), jnp.asarray(ds.base), batch=256)
    server = DarthServer(darth.engine, darth.trained.predictor,
                         darth.interval_for_target, num_slots=32,
                         steps_per_sync=2, tracer=tracer, metrics=metrics)
    return ds, server


def obs_tracing_overhead(repeats: int = 5):
    """p99 chunk-latency ratio, traced vs untraced, same workload."""
    from repro.obs import Tracer

    ds, base_server = _build()
    tracer = Tracer(traj_cap=64)
    _, traced_server = _build(tracer=tracer)
    rts = np.tile(np.asarray([0.8, 0.9, 0.95], np.float32),
                  ds.queries.shape[0])[:ds.queries.shape[0]]

    # warmup: compile both servers' chunk jits before timing anything
    base_server.serve(ds.queries, rts)
    traced_server.serve(ds.queries, rts)

    p99_off, p99_on = [], []
    for _ in range(repeats):
        _, s_off = base_server.serve(ds.queries, rts)
        _, s_on = traced_server.serve(ds.queries, rts)
        p99_off.append(s_off.chunk_ms_p99)
        p99_on.append(s_on.chunk_ms_p99)
    # best-of-repeats damps scheduler noise on shared CI hosts: the
    # minimum is the least-interfered run of each arm
    off, on = min(p99_off), min(p99_on)
    ratio = on / off if off > 0 else float("nan")
    spans = len(tracer.last_spans)

    rows = [{
        "queries": int(ds.queries.shape[0]), "repeats": repeats,
        "p99_off_ms": round(off, 3), "p99_on_ms": round(on, 3),
        "ratio": round(ratio, 4), "gate": OVERHEAD_GATE,
        "spans_per_serve": spans,
        "passed": bool(ratio <= OVERHEAD_GATE),
    }]
    headline = (f"tracing p99 {on:.2f} ms vs {off:.2f} ms off = "
                f"{ratio:.3f}x (gate {OVERHEAD_GATE}x, {spans} spans)")
    if not rows[0]["passed"]:
        raise AssertionError(
            f"tracing overhead gate failed: p99 ratio {ratio:.3f} > "
            f"{OVERHEAD_GATE} ({on:.3f} ms on vs {off:.3f} ms off)")
    return rows, headline


if __name__ == "__main__":
    from benchmarks.artifact import write_bench_artifact
    from repro.launch import compile_cache
    compile_cache.enable()
    out_rows, out_headline = obs_tracing_overhead()
    print(out_headline)
    print("wrote", write_bench_artifact(
        {"obs_tracing_overhead": {"headline": out_headline,
                                  "rows": out_rows}}))
