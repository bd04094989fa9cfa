"""ms: 99th percentile of latency over every query due in the window."""
from bench.metrics._lib import percentile


def read(run):
    return percentile(run.latencies_ms, 99) if run.latencies_ms is not None else None
