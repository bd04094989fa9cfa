"""%: queries that probed every list, closed-loop cells."""
from bench.metrics._lib import exhausted_share


def read(run):
    return exhausted_share(run)
