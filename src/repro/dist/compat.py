"""Mesh and shard_map constructors, in one place.

Everything in this repo (and the tests) builds meshes and shard_maps
through these three helpers, so the sharding rulebook is exercised one way:
mesh axes are `Auto`-typed, and shard_map runs without the replication
check (the sharded backend's bodies contain jit'd Pallas calls the checker
cannot see through). Written for the installed jax 0.9.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType


def make_compat_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """A concrete device mesh with Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """An AbstractMesh (no devices) — resolver logic against production
    shapes without needing the hardware."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))
