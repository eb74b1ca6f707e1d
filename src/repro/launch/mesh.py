"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run entry point
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import (see ``repro.launch.dryrun``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto``: the model's sharding constraints
    are hints for the partitioner, which ``Explicit`` axes (the
    ``jax.make_mesh`` default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1, devices=None):
    """Elastic mesh constructor for tests / small runs / scale-down, over
    ``devices`` (default: all of them)."""
    if pod > 1:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"),
                          devices)
    return _auto_mesh((data, model), ("data", "model"), devices)


def mesh_chip_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
