"""ms: median latency, due time to the return of the answering call, over every query due in the window."""
from bench.metrics._lib import percentile


def read(run):
    return percentile(run.latencies_ms, 50) if run.latencies_ms is not None else None
