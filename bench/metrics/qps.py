"""queries/s: queries completed in the window over its wall time."""
from bench.metrics._lib import qps


def read(run):
    return qps(run)
