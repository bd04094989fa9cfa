"""%: serve loop's host share of the serve calls, closed-loop cells."""
from bench.metrics._lib import host_share


def read(run):
    return host_share(run)
