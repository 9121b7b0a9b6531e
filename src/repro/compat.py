"""The JAX mesh/shard_map surface the repo uses, in one place.

Everything that builds meshes or enters shard_map imports from here,
never from jax directly, so a future API move touches one file.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P  # noqa: F401  (re-export)

__all__ = ["P", "axis_size", "make_mesh", "shard_map"]


def make_mesh(shape, axes):
    """Mesh with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=False):
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)


def axis_size(axes) -> int:
    """Product of the named mesh axis sizes (inside shard_map)."""
    return int(jax.lax.axis_size(axes))
