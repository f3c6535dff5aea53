"""The two jax mesh helpers this repo calls everywhere.

``make_auto_mesh`` builds a mesh with Auto axis types; ``shard_map`` wraps
``jax.shard_map`` with replication checking off (the call sites return
per-shard values on purpose). The code targets the installed jax (0.9) only.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_auto_mesh", "shard_map"]


def make_auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types. ``devices`` defaults to
    ``jax.devices()``; pass described devices to compile for a topology
    that is not attached."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes), **kw)


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
