"""Pallas TPU kernel: dense SWLC proximity block materialization.

For a (block_q × block_w) tile of the proximity matrix the kernel holds the
query tile as (block_q, T) and the reference tile *transposed* as
(T, block_w) in VMEM, and accumulates one masked rank-1 update per tree on
the VPU:

    acc += (q[:, t] ⊗ w[t, :]) ⊙ (gl_q[:, t] == gl_w[t, :])

Every update is a lane-dense (block_q, block_w) operation: the query column
broadcasts along lanes, the reference row along sublanes, and the tree
index is static, so no dynamic slice or 3-D intermediate is lowered.

Work is block_q·block_w·T per tile — i.e. the naive-pairwise cost, but only
for the *requested* blocks (visualization tiles, k-NN re-ranking, medoid
queries).  The full kernel never goes through here; it uses the factored
segment-sum path (core.jax_ops) which keeps the paper's O(N T λ̄) bound.

VMEM: double-buffered (block, T) input tiles of both sides plus the
(block_q, block_w) output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["block_prox_pallas"]


def _block_prox_kernel(glq_ref, q_ref, glwt_ref, wt_ref, out_ref):
    acc = jnp.zeros(out_ref.shape, out_ref.dtype)
    for t in range(glq_ref.shape[1]):     # static: one update per tree
        coll = glq_ref[:, t:t + 1] == glwt_ref[t:t + 1, :]
        acc = acc + jnp.where(coll, q_ref[:, t:t + 1] * wt_ref[t:t + 1, :],
                              0.0)
    out_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_w", "interpret",
                                    "dtype"))
def block_prox_pallas(gl_q: jax.Array, q: jax.Array, gl_w: jax.Array,
                      w: jax.Array, block_q: int = 256, block_w: int = 256,
                      interpret: bool = False,
                      dtype=jnp.float32) -> jax.Array:
    """(Nq, Nw) proximity block in ``dtype``; inputs as in ``ref.block_prox_ref``.

    float64 requires jax x64 mode and interpret mode (the TPU has no f64
    vector unit); ``ops.block_prox`` enforces that.
    """
    nq, T = gl_q.shape
    nw = gl_w.shape[0]
    nq_pad = (nq + block_q - 1) // block_q * block_q
    nw_pad = (nw + block_w - 1) // block_w * block_w
    # padded rows carry collision-free sentinel leaves and zero weight
    gl_q = jnp.pad(gl_q, ((0, nq_pad - nq), (0, 0)), constant_values=-1)
    q = jnp.pad(q.astype(dtype), ((0, nq_pad - nq), (0, 0)))
    gl_wt = jnp.pad(gl_w, ((0, nw_pad - nw), (0, 0)), constant_values=-2).T
    wt = jnp.pad(w.astype(dtype), ((0, nw_pad - nw), (0, 0))).T

    out = pl.pallas_call(
        _block_prox_kernel,
        grid=(nq_pad // block_q, nw_pad // block_w),
        in_specs=[
            pl.BlockSpec((block_q, T), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, T), lambda i, j: (i, 0)),
            pl.BlockSpec((T, block_w), lambda i, j: (0, j)),
            pl.BlockSpec((T, block_w), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_w), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq_pad, nw_pad), dtype),
        interpret=interpret,
    )(gl_q, q, gl_wt, wt)
    return out[:nq, :nw]
