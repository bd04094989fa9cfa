#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of BENCHMARK.json.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for. Set-up (data, index build, predictor fit, warm-up) counts from the
start of this process; then the window is measured for `--seconds`; then
every answer is checked against the plain reference. The last line of
standard output is the result (JSON); the compared numbers and their limits
are the last lines of standard error. Without a TPU, or with fewer chips
than the cell needs, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    from bench import harness
    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
