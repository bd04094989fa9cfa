"""%: the serve calls' time under `darth.serve.harvest` over their time
under `darth.serve`, traced runs of a program that emits the spans."""
from bench import program_trace


def read(run):
    return program_trace.span_share(run, "harvest")
