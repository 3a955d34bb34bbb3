"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Mesh of the given shape over this process's devices, Auto axes
    (GSPMD propagates shardings; e.g. (4, 2) for elastic re-meshing)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model); the ``pod``
    axis carries hierarchical data parallelism across the ICI/DCN boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
