"""The mesh and shard_map calls every sharded path in the repo makes.

Written for the installed JAX (0.9.0): meshes pin ``AxisType.Auto`` on
every axis, so today's sharding behaviour holds even if JAX's default
ever flips to Explicit, and ``shard_map`` spells its replication check
``check_vma``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types, over ``devices`` (default:
    all of them)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(shape), axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs,
              check_vma: bool = False):
    """``jax.shard_map``; the replication check is off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
