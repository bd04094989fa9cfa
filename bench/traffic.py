"""The one traffic generator: a mix file's parameters, the seed and the
window length give the queries handed to the server, their declared recall
targets and, in an open loop, when each is due.

A mix file (`bench/traffic/<mix>.json`) holds:

  loop          "closed": back-to-back serve calls of `call_queries`;
                "open": arrivals on a clock at `rate_qps`, each serve call
                takes every query due by the time the previous call returned
  targets       declared recall targets; pool query i declares
                targets[i % len(targets)], so each holds an equal share
  rate_qps      open loop: mean arrival rate

The query pool itself is drawn from the run's seed (bench/data.py). Here
the seed changes the order of the work, never its amount: a closed-loop
call serves a fixed slice of the pool (the pool cycled) in a seed-shuffled
order; open-loop arrival i is pool query i, and every seed gets the same
multiset of inter-arrival gaps (the quantiles of the exponential
distribution) in its own order.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class Plan(NamedTuple):
    loop: str
    call_queries: int             # closed loop: queries per serve call
    due_s: Optional[np.ndarray]   # open loop: due offsets from the start
    query_ids: np.ndarray         # open loop: pool index of each arrival
    targets: np.ndarray           # open loop: declared target per arrival


def targets_of(mix: dict, ids: np.ndarray) -> np.ndarray:
    """The declared target of each pool query in `ids`."""
    vals = np.asarray(mix["targets"], np.float32)
    return vals[np.asarray(ids) % vals.shape[0]]


def closed_call(mix: dict, seed: int, call: int, pool_size: int):
    """Pool indices and declared targets of closed-loop call number `call`:
    the call's slice of the cycled pool, in a seed-shuffled order."""
    n = int(mix["call_queries"])
    ids = (call * n + np.arange(n)) % pool_size
    ids = np.random.default_rng([seed, call]).permutation(ids)
    return ids, targets_of(mix, ids)


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Open-loop due offsets (seconds from the start), ascending."""
    rate = float(mix["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    # exponential quantiles: the same set of gaps for every seed
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(np.random.default_rng([seed, 2]).permutation(gaps))


def plan(mix: dict, seed: int, seconds: float, pool_size: int) -> Plan:
    if mix["loop"] == "closed":
        empty = np.zeros((0,), np.int64)
        return Plan("closed", int(mix["call_queries"]), None, empty,
                    np.zeros((0,), np.float32))
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    due = arrivals(mix, seed, seconds)
    ids = np.arange(due.shape[0]) % pool_size
    return Plan("open", 0, due, ids, targets_of(mix, ids))

