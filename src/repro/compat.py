"""Thin wrappers over the two JAX mesh APIs the repo uses (jax 0.9.0)."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axes):
    """`jax.make_mesh` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
