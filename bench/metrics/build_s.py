"""s: host clock around the IVF build (k-means, assignment, packing)."""


def read(run):
    return run.setup_parts.get("build_s")
