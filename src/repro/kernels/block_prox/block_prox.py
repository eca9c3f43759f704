"""Pallas TPU kernel: dense SWLC proximity blocks, tiled over trees.

For a (block_q × block_w) tile of the proximity matrix the kernel walks the
trees in chunks of ``t_chunk`` (a grid axis marked ``"arbitrary"``) and
adds one masked rank-1 update per tree to a VMEM accumulator on the VPU:

    acc += (q[:, t] ⊗ w[t, :]) ⊙ (gl_q[:, t] == gl_w[t, :])

Every update is a lane-dense (block_q, block_w) operation: the query column
broadcasts along lanes, the reference row along sublanes, and the tree
index within a chunk is static, so no dynamic slice or 3-D intermediate is
lowered.  Each update is added to the accumulator as it is made, so the
scoped VMEM a call needs depends on neither T nor the chunk
(``block_vmem_bytes``); T is padded up to the chunk with collision-free
sentinel trees (query −1 against reference −2, weight 0).  The tile is
written on the last chunk.

Layouts, as ``pad_query`` / ``pad_reference`` build them: the query side is
(n_chunks, Nq_pad, t_chunk), so a block's last dim is the whole chunk; the
reference side is transposed to (T_pad, Nw_pad), so its block is
(t_chunk, block_w).  ``pad_reference`` depends only on the reference rows:
a caller that queries one reference set many times stages it once and
calls ``block_prox_padded``.

Work is block_q·block_w·T per tile — i.e. the naive-pairwise cost, but only
for the *requested* blocks (visualization tiles, k-NN queries, outlier
sums).  The full kernel never goes through here; it uses the factored
segment-sum path (core.jax_ops) which keeps the paper's O(N T λ̄) bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_prox_pallas", "block_prox_padded", "pad_query",
           "pad_reference", "block_vmem_bytes", "BLOCK_Q", "BLOCK_W",
           "T_CHUNK"]

# Tile and chunk sizes (TPU v5e, 1,024 × 100 trees against 100,000 rows):
# a query column is broadcast across the tile's lanes once per tree, so a
# wider reference tile amortizes it over more columns.
BLOCK_Q = 256        # rows of a query tile
BLOCK_W = 1024       # columns of a reference tile
T_CHUNK = 8          # trees per grid step: one sublane tile of the reference


def _padded(rows: int, cols: int) -> int:
    """4-byte elements of a 2-D VMEM buffer in (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128)


def block_vmem_bytes(block_q: int, block_w: int, t_chunk: int) -> int:
    """Upper bound on the scoped VMEM one kernel invocation allocates, in
    bytes: the double-buffered query (block_q, t_chunk) and reference
    (t_chunk, block_w) blocks of ids and weights, the accumulator, the
    double-buffered output tile and two tiles of one update's temporaries
    (its mask and product).  ``tests/test_tpu_compile.py`` compiles the
    kernel for v5e with this number as the compiler's scoped-VMEM
    limit."""
    tile = _padded(block_q, block_w)
    inputs = 4 * (_padded(block_q, t_chunk) + _padded(t_chunk, block_w))
    return 4 * (inputs + 5 * tile)


@functools.partial(jax.jit, static_argnames=("block_q", "t_chunk", "dtype"))
def pad_query(gl_q: jax.Array, q: jax.Array, block_q: int, t_chunk: int,
              dtype) -> tuple:
    """Query ids and weights as (n_chunks, Nq_pad, t_chunk); padded rows and
    trees carry sentinel leaf −1 and weight 0."""
    nq, T = gl_q.shape
    pr, pt = (-nq) % block_q, (-T) % t_chunk
    gl = jnp.pad(gl_q, ((0, pr), (0, pt)), constant_values=-1)
    x = jnp.pad(q.astype(dtype), ((0, pr), (0, pt)))
    split = lambda a: a.reshape(a.shape[0], -1, t_chunk).transpose(1, 0, 2)
    return split(gl), split(x)


@functools.partial(jax.jit, static_argnames=("block_w", "t_chunk", "dtype"))
def pad_reference(gl_w: jax.Array, w: jax.Array, block_w: int, t_chunk: int,
                  dtype) -> tuple:
    """Reference ids and weights transposed to (T_pad, Nw_pad); padded rows
    and trees carry sentinel leaf −2 and weight 0."""
    nw, T = gl_w.shape
    pad = ((0, (-nw) % block_w), (0, (-T) % t_chunk))
    return (jnp.pad(gl_w, pad, constant_values=-2).T,
            jnp.pad(w.astype(dtype), pad).T)


def _block_kernel(glq_ref, q_ref, glwt_ref, wt_ref, out_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    glq, q = glq_ref[0], q_ref[0]              # (block_q, t_chunk)
    for t in range(glq.shape[1]):              # static: one chunk unrolled
        coll = glq[:, t:t + 1] == glwt_ref[t:t + 1, :]
        acc_ref[...] += jnp.where(coll, q[:, t:t + 1] * wt_ref[t:t + 1, :],
                                  0.0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _write():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_w", "interpret"))
def block_prox_padded(glq: jax.Array, q: jax.Array, glwt: jax.Array,
                      wt: jax.Array, block_q: int = BLOCK_Q,
                      block_w: int = BLOCK_W,
                      interpret: bool = False) -> jax.Array:
    """(Nq_pad, Nw_pad) proximity block of ``pad_query`` /
    ``pad_reference`` layouts (one dtype, the output's); padded rows and
    columns read 0."""
    n_chunks, nq_pad, t_chunk = glq.shape
    nw_pad = glwt.shape[1]
    return pl.pallas_call(
        _block_kernel,
        grid=(nq_pad // block_q, nw_pad // block_w, n_chunks),
        in_specs=[
            pl.BlockSpec((1, block_q, t_chunk), lambda i, j, c: (c, i, 0)),
            pl.BlockSpec((1, block_q, t_chunk), lambda i, j, c: (c, i, 0)),
            pl.BlockSpec((t_chunk, block_w), lambda i, j, c: (c, j)),
            pl.BlockSpec((t_chunk, block_w), lambda i, j, c: (c, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_w), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq_pad, nw_pad), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, block_w), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=block_vmem_bytes(block_q, block_w, t_chunk)),
        interpret=interpret,
    )(glq, q, glwt, wt)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_w", "interpret",
                                    "dtype"))
def block_prox_pallas(gl_q: jax.Array, q: jax.Array, gl_w: jax.Array,
                      w: jax.Array, block_q: int = BLOCK_Q,
                      block_w: int = BLOCK_W, interpret: bool = False,
                      dtype=jnp.float32) -> jax.Array:
    """(Nq, Nw) proximity block in ``dtype``; inputs as in ``ref.block_prox_ref``.

    float64 requires jax x64 mode and interpret mode (the TPU has no f64
    vector unit); ``ops.block_prox`` enforces that.
    """
    glq, qq = pad_query(gl_q, q, block_q, T_CHUNK, dtype)
    glwt, wt = pad_reference(gl_w, w, block_w, T_CHUNK, dtype)
    out = block_prox_padded(glq, qq, glwt, wt, block_q, block_w, interpret)
    return out[:gl_q.shape[0], :gl_w.shape[0]]
