#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: one set-up, then the cell's open-loop
window at each of several arrival rates, on the chip.

  python3 bench/sweep.py --workload <open-loop cell> --seed <n> \
      --seconds <s> --rates 300,500,700

Per rate it prints p50 and p99 latency over every query due, the number and
mean size of the serve calls, how long the run outlasted its arrivals, and
the ratio of the median latency of the last fifth of the arrivals to the
first fifth (a backlog that grows through the run reads well above 1). The
cell's fixed rate is chosen from this once, and written into its traffic
file; the benchmark's runs never sweep.
"""
import argparse
import contextlib
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import jax
    import numpy as np

    from bench import harness, traffic
    from bench.metrics import _lib

    cell, cfg, mix = harness.resolve(harness.load_spec(), args.workload)
    if mix["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    try:
        harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"[device] {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    parts = {}
    server, _, pool = harness.build_system(cfg, args.seed, parts,
                                           mix["targets"])
    lowered = [0]

    def count(event: str, duration_secs: float, **_) -> None:
        if event == harness.LOWERING_EVENT:
            lowered[0] += 1
    jax.monitoring.register_event_duration_secs_listener(count)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.plan(dict(mix, rate_qps=rate), args.seed,
                            args.seconds, pool.shape[0])
        t0, before = time.perf_counter(), lowered[0]
        calls, _, window_s, lat = harness.open_window(
            server, cfg, plan, pool, None,
            lambda name: contextlib.nullcontext())
        fifth = max(1, lat.shape[0] // 5)
        growth = np.median(lat[-fifth:]) / np.median(lat[:fifth])
        print(f"[sweep] rate {rate:g}/s: p50 {_lib.percentile(lat, 50):.1f}"
              f" ms, p99 {_lib.percentile(lat, 99):.1f} ms over "
              f"{lat.shape[0]} queries; {len(calls)} calls of mean "
              f"{np.mean([c.queries for c in calls]):.1f} queries; run "
              f"outlasted its arrivals by {window_s - plan.due_s[-1]:.3f} s;"
              f" late/early median latency {growth:.2f} "
              f"({time.perf_counter() - t0:.1f} s), programs lowered "
              f"{lowered[0] - before}; calls (queries, s, engine steps): "
              + " ".join(f"{c.queries},{c.t1 - c.t0:.3f},{c.engine_steps}"
                         for c in calls),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
