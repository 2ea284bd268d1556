"""Production mesh construction.

A function, not a module constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

__all__ = ["make_production_mesh", "make_mesh", "data_axes_of",
           "MODEL_AXIS"]

MODEL_AXIS = "model"


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips).

    Batch shards over ("pod", "data"); weights/experts/vocab over
    "model".  The dry-run proves both lower + compile for every
    (architecture x input shape).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
