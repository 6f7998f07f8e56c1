"""Production meshes (task spec). A FUNCTION, not a module constant, so
importing never touches jax device state."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axes: the shard_map programs here place
    their operands by in_specs, and jax.make_mesh's default Explicit axes
    would type-check every sharding against the mesh instead."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
