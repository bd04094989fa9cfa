#!/usr/bin/env python3
"""CPU rehearsal of benchmark cells at a tiny size, with interpreted kernels.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <cell> ...]

Runs each cell (every cell of BENCHMARK.json by default) through the same
harness as `bench/run.py`: set-up, window, trace, every metric reader of the
cell and the check against the reference, at a size a CPU holds. It prints
which readers returned a value and whether the run was correct, never a
metric value or a result line: a CPU run is not a chip run.
"""
import argparse
import copy
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# A rehearsal keeps every key of the configuration and the mix and only
# makes them small.
SIZES = dict(n=4096, pool_queries=128)
INDEX = dict(nlist=16, nprobe=16, cap=8)
SERVING = dict(num_slots=16)
FIT = dict(learn_queries=96, max_samples=20000)
DATA = dict(components=16)
CALL_QUERIES = 48
RATE_QPS = 40.0
SECONDS = 2.0


def shrink(cfg: dict, mix: dict):
    cfg, mix = copy.deepcopy(cfg), copy.deepcopy(mix)
    cfg.update(SIZES)
    cfg["index"].update(INDEX)
    cfg["serving"].update(SERVING)
    cfg["fit"].update(FIT)
    cfg["data"].update(DATA)
    if "call_queries" in mix:
        mix["call_queries"] = CALL_QUERIES
    if "rate_qps" in mix:
        mix["rate_qps"] = RATE_QPS
    return cfg, mix


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args()
    from bench import harness
    spec = harness.load_spec()
    names = args.workload or [w["name"] for w in spec["workloads"]]
    bad = 0
    for name in names:
        rc = harness.run_cell(name, args.seed, SECONDS, True,
                              t_start=time.perf_counter(), spec=spec,
                              rehearsal=True, adjust=shrink)
        bad += rc != 0
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
