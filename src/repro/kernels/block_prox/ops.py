"""Jit'd wrapper for SWLC block materialization."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .block_prox import BLOCK_Q, BLOCK_W, block_prox_pallas
from .ref import block_prox_ref

__all__ = ["block_prox"]


def block_prox(gl_q, q, gl_w, w, block_q: int = BLOCK_Q,
               block_w: int = BLOCK_W,
               use_pallas: bool = True, dtype=jnp.float32) -> jax.Array:
    """``dtype`` selects the accumulator/output precision.

    The compiled kernel is float32 only: asking it for float64 raises
    (callers that want an f64 result on an accelerator pass float32 and
    upcast, saying so).  In interpret mode float64 needs jax x64 mode.
    """
    interpret = interpret_mode()
    if use_pallas and not interpret and jnp.dtype(dtype) != jnp.float32:
        raise ValueError(
            f"compiled block_prox computes in float32 only, got {dtype}; "
            "pass dtype=float32")
    gl_q = jnp.asarray(gl_q, jnp.int32)
    gl_w = jnp.asarray(gl_w, jnp.int32)
    q = jnp.asarray(q, dtype)
    w = jnp.asarray(w, dtype)
    if not use_pallas:
        return block_prox_ref(gl_q, q, gl_w, w)
    # compiled, the kernel is 32-bit throughout (int32 index maps)
    with contextlib.nullcontext() if interpret else jax.enable_x64(False):
        return block_prox_pallas(gl_q, q, gl_w, w, block_q=block_q,
                                 block_w=block_w, interpret=interpret,
                                 dtype=dtype)
