#!/usr/bin/env python3
"""The check's control: a cell served with the program's own int8 (SQ8)
bucket store (`index.store` "int8", `repro.index.ivf.build(quantize=True)`)
in place of the float32 store the configuration states, everything else as
in `bench/run.py`. Its `correct` has to come out false.

  python3 bench/control.py --workload <cell> --seeds <n,n,...> --seconds <s>

Each seed is one whole run (its own collection, index, fit and window),
one after another in this process; each prints its checks and result line.
The benchmark's own runs never run it; PERF.md records its readings.
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

STORE = "int8"


def lower(cfg: dict, mix: dict):
    """The control's configuration: the SQ8 store."""
    from bench import harness
    return harness.with_store(cfg, STORE), mix


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench import harness
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        print(f"[control] {args.workload} seed {seed}, store {STORE}",
              flush=True)
        rc = max(rc, harness.run_cell(args.workload, seed, args.seconds,
                                      False, t_start=time.perf_counter(),
                                      adjust=lower))
    return rc


if __name__ == "__main__":
    sys.exit(main())
