"""Production mesh factory (deliverable e).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first jax init, and
tests/benches must see 1 CPU device while the dry-run sees 512
placeholders).

Every mesh is built with Auto axes (`auto_mesh`): jax.make_mesh defaults
to Explicit axes, which with_sharding_constraint and the GSPMD-placed
serving state reject."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def auto_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """jax.make_mesh with every axis Auto (sharding propagated by GSPMD).
    `devices` defaults to all visible devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh with the production axis names (smoke tests)."""
    return auto_mesh((1, 1), ("data", "model"))


def make_search_mesh(num_shards: int = 0):
    """1-D ("model",) mesh for sharded ANN search (dist/collectives.py).

    `num_shards` 0 means "all visible devices"; the database rows are
    sharded over this axis, queries replicate. On the 1-CPU test host
    this is a (1,) mesh and the search path is identical."""
    n = num_shards or jax.device_count()
    if jax.device_count() < n:
        raise ValueError(
            f"--shards {n} needs {n} devices but only "
            f"{jax.device_count()} visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} for a smoke run")
    return auto_mesh((n,), ("model",))


def make_serve_mesh(hosts: int = 1, shards: int = 0):
    """2-D ("hosts", "model") mesh for multi-host slot-pool serving.

    The "model" axis shards the index (dist/collectives.py fast paths,
    same as make_search_mesh); the "hosts" axis carries the slot dim of
    the serve batch (dist.sharding.batch_shardings kind="serve" /
    slot_sharding), so each host group's devices step only the slot
    slice its host loop owns and the per-chunk collectives run within a
    host group. `shards` 0 means "all remaining devices per host"."""
    if hosts < 1:
        raise ValueError(f"hosts must be >= 1, got {hosts}")
    n = shards or max(jax.device_count() // hosts, 1)
    if jax.device_count() < hosts * n:
        raise ValueError(
            f"--hosts {hosts} x --shards {n} needs {hosts * n} devices "
            f"but only {jax.device_count()} visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={hosts * n} for a "
            f"smoke run")
    return auto_mesh((hosts, n), ("hosts", "model"))


def describe(mesh) -> str:
    return f"mesh{tuple(mesh.shape.values())} axes={mesh.axis_names}"
