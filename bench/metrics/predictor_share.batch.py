"""%: device self time in the predictor scope (`darth.predict`) over busy
time, traced runs of a program that carries the scope."""
from bench import program_trace


def read(run):
    return program_trace.scope_share(run, ("darth.predict",))
