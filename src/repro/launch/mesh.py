"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state; the dry-run sets
``xla_force_host_platform_device_count`` before calling it.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 ("data","model"). Multi-pod: 2x16x16 ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices: Sequence | None = None) -> Mesh:
    """Mesh with Auto axes (jax.make_mesh defaults to Explicit ones, which
    the sharding-constraint and shard_map code here does not use). With
    ``devices``, the mesh spans exactly those devices instead of the first
    ``prod(shape)`` of ``jax.devices()``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
