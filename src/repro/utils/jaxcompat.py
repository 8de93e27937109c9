"""The two JAX entry points the package shares across its meshes.

* ``shard_map``: ``jax.shard_map`` (``check_vma=False`` turns off its
  replication check).
* ``make_auto_mesh``: ``jax.make_mesh`` with every axis ``AxisType.Auto``.
"""
from __future__ import annotations

import jax
from jax import shard_map


def make_auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
                   devices=None) -> jax.sharding.Mesh:
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


__all__ = ["shard_map", "make_auto_mesh"]
