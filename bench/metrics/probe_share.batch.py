"""%: device self time in the probe step's scopes (`darth.probe` with its
`darth.merge`) over busy time, traced runs of a program that carries them."""
from bench import program_trace


def read(run):
    return program_trace.scope_share(run, ("darth.probe", "darth.merge"))
