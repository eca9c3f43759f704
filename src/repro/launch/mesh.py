"""Production mesh construction (brief: MULTI-POD DRY-RUN step 1).

A function, not a module-level constant — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "compat_mesh",
           "batch_axes", "fsdp_axes", "MODEL_AXIS"]

MODEL_AXIS = "model"


def compat_mesh(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    return compat_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh) -> tuple:
    """Mesh axes parameters are fully-sharded (ZeRO-3) over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
