"""Mesh helpers shared by the sharded paths.

One place fixes the arguments the installed jax wants for a shard_map and
a mesh (no replication check; Auto axis types), so call sites never spell
them out.
"""

from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs):
    """jax.shard_map with the vma check off."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
