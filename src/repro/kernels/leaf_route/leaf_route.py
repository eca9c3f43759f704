"""Pallas TPU kernel: batched root-to-leaf routing.

TPU adaptation of pointer-chasing tree traversal (DESIGN.md §3): the grid
walks sample tiles; each program holds its (block_n, D) sample tile and the
whole (T, M) struct-of-arrays node tables in VMEM, and routes the tile
through every tree with ``max_depth`` branch-free steps of gather + compare
+ select, writing one (block_n, T) output block.  Whole-array node blocks
and a full-T output block keep every block shape legal under the (8, 128)
tiling rule.

The per-sample gathers from the node tables (``feat[node]``) and from the
sample row (``x[i, feat]``) are what the v5e compiler refuses to lower, so
on a TPU this kernel raises the compiler's error: exact device routing is
open work, and the host routes instead.  Interpret mode runs it anywhere.

VMEM budget per program: block_n·D·4 (samples) + 5·T·M·4 (nodes) +
block_n·T·4 (output) bytes, double-buffered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["route_pallas"]


def _route_kernel(x_ref, feat_ref, thr_ref, left_ref, right_ref, lid_ref,
                  out_ref, *, max_depth: int):
    x = x_ref[...]                      # (block_n, D)
    n = x.shape[0]
    for t in range(feat_ref.shape[0]):  # static: one pass per tree
        feat, thr = feat_ref[t], thr_ref[t]          # (M,)
        left, right = left_ref[t], right_ref[t]

        def body(_, node):
            f = feat[node]                          # gather over nodes
            internal = f >= 0
            fi = jnp.where(internal, f, 0)
            xv = jnp.take_along_axis(x, fi[:, None], axis=1)[:, 0]
            go_left = xv <= thr[node]
            nxt = jnp.where(go_left, left[node], right[node])
            return jnp.where(internal, nxt, node).astype(jnp.int32)

        node = jax.lax.fori_loop(0, max_depth, body,
                                 jnp.zeros((n,), dtype=jnp.int32))
        out_ref[:, t:t + 1] = lid_ref[t][node][:, None]


@functools.partial(jax.jit, static_argnames=("max_depth", "block_n", "interpret"))
def route_pallas(x: jax.Array, feature: jax.Array, threshold: jax.Array,
                 left: jax.Array, right: jax.Array, leaf_id: jax.Array,
                 max_depth: int, block_n: int = 1024,
                 interpret: bool = False) -> jax.Array:
    """(N, T) int32 leaf ids.  Shapes as in ``ref.route_ref``."""
    n, d = x.shape
    T, m = feature.shape
    n_pad = (n + block_n - 1) // block_n * block_n
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    node_spec = pl.BlockSpec((T, m), lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(_route_kernel, max_depth=max_depth),
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((block_n, d), lambda i: (i, 0))]
        + [node_spec] * 5,
        out_specs=pl.BlockSpec((block_n, T), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, T), jnp.int32),
        interpret=interpret,
    )(x, feature, threshold, left, right, leaf_id)
    return out[:n]
