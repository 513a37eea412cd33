"""The repo's one spelling of the JAX sharding APIs it uses with
non-default settings (JAX 0.9, see requirements-dev.txt)."""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axes (JAX's default is Explicit)."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
