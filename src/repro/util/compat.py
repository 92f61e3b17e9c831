"""Mesh and shard_map spellings shared by every schedule (JAX 0.9)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default: the
    schedules' per-device panels are deliberately device-varying."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(shape, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types, so shardings inside jit are
    left to the partitioner as the schedules expect."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)
