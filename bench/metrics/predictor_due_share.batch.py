"""%: the batched predictor's slot evaluations that a slot was due for
(predictor calls over predictor batches x slots), traced runs of a program
that puts its counters on its `darth.serve` spans."""
from bench import program_trace


def read(run):
    return program_trace.due_share(run)
