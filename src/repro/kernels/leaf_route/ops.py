"""Jit'd public wrapper for the routing kernel.

Interpret mode on the CPU backend; compiled elsewhere — where the v5e
compiler refuses the kernel's gathers, so ``use_pallas`` raises its error
on a TPU instead of quietly routing some other way.
"""
from __future__ import annotations

import jax
import numpy as np

from ...forest.trees import TreeArrays
from .. import interpret_mode
from .leaf_route import route_pallas
from .ref import route_ref

__all__ = ["route", "route_arrays"]


def route_arrays(x, feature, threshold, left, right, leaf_id, max_depth,
                 block_n: int = 1024, use_pallas: bool = True):
    if not use_pallas:
        return route_ref(x, feature, threshold, left, right, leaf_id,
                         max_depth)
    with jax.enable_x64(False):        # 32-bit kernel: int32 index maps
        return route_pallas(x, feature, threshold, left, right, leaf_id,
                            max_depth=max_depth, block_n=block_n,
                            interpret=interpret_mode())


def route(x: np.ndarray, ta: TreeArrays, block_n: int = 1024,
          use_pallas: bool = True) -> np.ndarray:
    """Route samples through a padded ensemble. Returns (N, T) leaf ids."""
    import jax.numpy as jnp
    out = route_arrays(
        jnp.asarray(x, jnp.float32), jnp.asarray(ta.feature),
        jnp.asarray(ta.threshold), jnp.asarray(ta.left),
        jnp.asarray(ta.right), jnp.asarray(ta.leaf_id),
        max_depth=int(ta.max_depth), block_n=block_n, use_pallas=use_pallas)
    return np.asarray(out)
