"""steps/query: slot-steps per completed query, closed-loop cells."""
from bench.metrics._lib import steps_per_query


def read(run):
    return steps_per_query(run)
