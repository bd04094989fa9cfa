"""%: device idle share of the traced window, closed-loop cells."""
from bench.metrics._lib import idle_share


def read(run):
    return idle_share(run)
