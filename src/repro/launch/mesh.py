"""Mesh builder. A function, not module-level state, so importing this
module never touches jax device state."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices: Sequence | None = None) -> Mesh:
    """Mesh with Auto axes (jax.make_mesh defaults to Explicit ones, which
    the sharding-constraint and shard_map code here does not use). With
    ``devices``, the mesh spans exactly those devices instead of the first
    ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
