"""%: least time of the probed bucket rows over device busy time."""
from bench.metrics._lib import probe_roofline


def read(run):
    return probe_roofline(run)
