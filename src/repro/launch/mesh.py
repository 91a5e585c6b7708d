"""Production mesh definition.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips across 2 pods — the
"pod" axis carries pure data parallelism (gradient all-reduce crosses the
inter-pod DCN/ICI boundary once per step).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (jax defaults to
    ``Explicit``): the repo shards by ``shard_map`` and GSPMD hints, and
    relies on the compiler propagating shardings across the rest."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)
