"""Mesh construction (functions, not module constants — importing this
module never touches jax device state).

Every mesh has ``Auto`` axes: the model code places arrays with
``with_sharding_constraint`` and lets the partitioner propagate, which
``jax.make_mesh``'s default ``Explicit`` axes refuse."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for subprocess tests (requires >= n_data*n_model devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
