"""s: from process start to the window (data, build, fit, warm-up)."""


def read(run):
    return run.setup_s
