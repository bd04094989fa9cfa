"""Arithmetic shared by the metric readers (`read(run)` in each file)."""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import roofline


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile over every value, the nearest observed value at
    or above the rank for a tail (q > 50), interpolated for the median."""
    xs = np.asarray(values, np.float64)
    if xs.size == 0:
        return None
    method = "higher" if q > 50 else "linear"
    return float(np.percentile(xs, q, method=method))


def qps(run) -> Optional[float]:
    """Queries completed in the window over the window's wall time."""
    if not run.calls or run.window_s <= 0:
        return None
    return sum(c.completed for c in run.calls) / run.window_s


def host_share(run) -> Optional[float]:
    """Share (%) of the serve calls' wall time outside the server's chunk
    round-trips (dispatch plus the sync fetch): admission, harvest,
    refill and transfers on the host."""
    if not run.calls or any(c.chunk_ms_sum is None for c in run.calls):
        return None
    wall = sum(c.t1 - c.t0 for c in run.calls)
    chunks = sum(c.chunk_ms_sum for c in run.calls) * 1e-3
    return 100.0 * (1.0 - chunks / wall) if wall > 0 else None


def steps_per_query(run) -> Optional[float]:
    """Slot-steps the server spent per query it completed."""
    done = sum(c.completed for c in run.calls)
    return sum(c.slot_steps for c in run.calls) / done if done else None


def exhausted_share(run) -> Optional[float]:
    """Share (%) of the completed queries that never met their declared
    target early and probed every list (nprobe engine steps or more)."""
    if not run.calls or any(c.exhausted is None for c in run.calls):
        return None
    done = sum(c.completed for c in run.calls)
    return 100.0 * sum(c.exhausted for c in run.calls) / done if done else None


def idle_share(run) -> Optional[float]:
    """Share (%) of the traced window with no operation on the device."""
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def probe_roofline(run) -> Optional[float]:
    """Least time of the probe's work in the window (the real bucket rows
    probed, `ndis`) over the device's busy time there, in %."""
    t = run.trace
    if not t or t["busy_s"] <= 0:
        return None
    rows = sum(c.ndis for c in run.calls)
    flops, nbytes = roofline.probe_work(rows, run.config["dim"])
    least = roofline.least_seconds(flops, nbytes, run.device_kind)
    return 100.0 * least / t["busy_s"]
