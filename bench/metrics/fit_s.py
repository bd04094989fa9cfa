"""s: the GBDT part of Darth.fit (host clock, as the program reports it)."""


def read(run):
    return run.setup_parts.get("fit_s")
