"""JAX's persistent compilation cache for the program's entry points.

Called from `main` of the launchers, the benchmarks and `chip_smoke.py`,
never when a library module is imported. Where `JAX_COMPILATION_CACHE_DIR`
is set, JAX reads it itself and nothing is set in code. Otherwise the
cache lives at a fixed path inside the checkout, `<repo>/.jax_cache`
(ignored by git): the directory is part of the cache key, so a temp,
pid- or time-based path would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
