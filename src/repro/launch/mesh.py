"""Production meshes (brief: MULTI-POD DRY-RUN step 1).

A function, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

from jax import make_mesh
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes,
                     axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 4):
    """Small host-device mesh for integration tests (8 devices)."""
    return make_mesh((n_data, n_model), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
