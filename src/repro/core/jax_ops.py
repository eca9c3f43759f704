"""TPU-native SWLC operations in JAX (DESIGN.md §3).

On TPU we avoid CSR scatter/gather entirely.  The factored kernel apply
``P v = Q (Wᵀ v)`` becomes two dense-indexable primitives:

  1. bucket:  s[leaf] = Σ_{(i,t): gl[i,t]=leaf} w[i,t] · v[i]   (segment_sum)
  2. gather:  (Pv)[i] = Σ_t q[i,t] · s[gl[i,t]]

Both are O(N·T) with no data-dependent shapes, so they jit/pjit cleanly.
They are separate programs (``swlc_bucket``, ``swlc_gather``): the table
s = Wᵀ v depends only on the reference side, so a caller that applies one
v to many query batches builds it once and keeps it on the device.
Each stage runs under a ``jax.named_scope`` of its name (``bucket``,
``gather``), which the compiled HLO carries in its ``op_name`` metadata,
so a device trace can tell the two stages' operations apart.
The distributed version shards samples over the "data" mesh axis and trees
over the "model" mesh axis: each model shard buckets its own tree slice into
a private leaf-range (leaf ids are tree-major), so the only collectives are
a psum over "model" for the final gather-side reduction and a psum over
"data" inside downstream reductions.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["swlc_bucket", "swlc_gather", "swlc_block_topk",
           "sharded_swlc_matmat", "default_mesh"]


def default_mesh(data_axis: str = "data",
                 model_axis: str = "model") -> Optional[Mesh]:
    """(n_devices, 1) data-parallel mesh over all local devices, or None on a
    single device — the gate for the engine's sharded matmat path."""
    import numpy as np
    devs = jax.devices()
    if len(devs) < 2:
        return None
    return Mesh(np.asarray(devs).reshape(len(devs), 1),
                (data_axis, model_axis))


def auto_c_chunk(n_local: int, T: int, C: int,
                 budget_elems: int = 1 << 24) -> Optional[int]:
    """Column-chunk size for the *sharded* matmat, whose per-device
    (n_local, T, c_chunk) intermediate cannot tree-chunk (the bucket psum
    spans all trees); wide V is split into column blocks instead
    (None = no chunking)."""
    if n_local * T * C <= budget_elems:
        return None
    return max(1, min(C, budget_elems // max(n_local * T, 1)))


def _pad_trees(gl: jax.Array, x: jax.Array, total_leaves: int,
               t_chunk: int) -> Tuple[jax.Array, jax.Array, int]:
    """(gl, x, n_chunks) with the tree columns padded to a multiple of
    ``t_chunk``: sentinel columns of leaf id ``total_leaves`` (a dedicated
    padding bucket) and weight 0, which contribute nothing on either side."""
    pad = (-gl.shape[1]) % t_chunk
    if pad:
        gl = jnp.pad(gl, ((0, 0), (0, pad)), constant_values=total_leaves)
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return gl, x, gl.shape[1] // t_chunk


def _tree_chunk(a: jax.Array, c, t_chunk: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(a, c * t_chunk, t_chunk, axis=1)


@functools.partial(jax.jit, static_argnames=("total_leaves", "t_chunk"))
def swlc_bucket(gl_w: jax.Array, w: jax.Array, V: jax.Array,
                total_leaves: int,
                t_chunk: Optional[int] = None) -> jax.Array:
    """Reference bucket table S = Wᵀ V of reference rows (gl_w, w):
    (total_leaves + 1, C), the last row the (zero) padding bucket.

    It depends only on the reference side, so a caller applying the same V
    to many query batches builds it once (``ProximityEngine._ref_table``).
    ``t_chunk`` accumulates over tree chunks of that size, so the dense
    intermediate is (N_w, t_chunk, C) and each scatter has N_w·t_chunk
    rows, which is what the TPU compiler's time grows with: the engine
    passes 1.
    """
    nw, T = gl_w.shape
    C = V.shape[1]
    with jax.named_scope("bucket"):
        if t_chunk is None or t_chunk >= T:
            contrib = w[:, :, None] * V[:, None, :]          # (N_w, T, C)
            return jax.ops.segment_sum(contrib.reshape(nw * T, -1),
                                       gl_w.ravel(),
                                       num_segments=total_leaves + 1)
        gl_w, w, n_chunks = _pad_trees(gl_w, w, total_leaves, t_chunk)

        def bucket(c, s):
            contrib = _tree_chunk(w, c, t_chunk)[:, :, None] * \
                V[:, None, :]                            # (N_w, t_chunk, C)
            return s + jax.ops.segment_sum(
                contrib.reshape(nw * t_chunk, -1),
                _tree_chunk(gl_w, c, t_chunk).ravel(),
                num_segments=total_leaves + 1)

        return jax.lax.fori_loop(
            0, n_chunks, bucket,
            jnp.zeros((total_leaves + 1, C),
                      dtype=jnp.result_type(w.dtype, V.dtype)))


@functools.partial(jax.jit, static_argnames=("t_chunk",))
def swlc_gather(gl_q: jax.Array, q: jax.Array, S: jax.Array,
                t_chunk: Optional[int] = None) -> jax.Array:
    """(P V)[i] = Σ_t q[i,t] · S[gl_q[i,t]] for query rows (gl_q, q) and a
    bucket table S of ``swlc_bucket``: (N_q, C).  ``t_chunk`` as there."""
    nq, T = gl_q.shape
    with jax.named_scope("gather"):
        if t_chunk is None or t_chunk >= T:
            return (q[:, :, None] * S[gl_q]).sum(axis=1)
        gl_q, q, n_chunks = _pad_trees(gl_q, q, S.shape[0] - 1, t_chunk)

        def gather(c, out):
            qq = _tree_chunk(q, c, t_chunk)
            return out + (qq[:, :, None] *
                          S[_tree_chunk(gl_q, c, t_chunk)]).sum(axis=1)

        return jax.lax.fori_loop(
            0, n_chunks, gather,
            jnp.zeros((nq, S.shape[1]),
                      dtype=jnp.result_type(q.dtype, S.dtype)))


@functools.partial(jax.jit, static_argnames=("total_leaves", "t_chunk"))
def _swlc_product(gl_q: jax.Array, q: jax.Array, gl_w: jax.Array,
                  w: jax.Array, V: jax.Array, total_leaves: int,
                  t_chunk: Optional[int]) -> jax.Array:
    """(P V) for P = SWLC(q, w) with query rows (gl_q, q) and reference rows
    (gl_w, w); V: (N_w, C): the bucket stage, then the gather, in one
    program."""
    return swlc_gather(gl_q, q, swlc_bucket(gl_w, w, V, total_leaves,
                                            t_chunk), t_chunk)


@functools.partial(jax.jit, static_argnames=("k", "n_ref", "interpret"))
def swlc_block_topk(gl_q: jax.Array, q: jax.Array, glwt: jax.Array,
                    wt: jax.Array, k: int, n_ref: int,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Top-k proximities of query rows (gl_q, q), each (N_q, T), against
    reference factors staged once in the block kernel's layout
    (``block_prox.pad_reference``: (T_pad, N_w_pad)), of which the first
    ``n_ref`` columns are real.  Returns (values, int32 indices), (N_q, k),
    values descending.

    Two stages, each under a ``jax.named_scope`` a device trace can read:
    ``block``, the tree-tiled Pallas kernel's dense (N_q, N_w) block, and
    ``select``, ``lax.top_k`` over its real columns.  Only the (N_q, k)
    answer leaves the device.
    """
    from ..kernels.block_prox.block_prox import (BLOCK_Q, T_CHUNK,
                                                 block_prox_padded, pad_query)
    with jax.named_scope("block"):
        glq, qq = pad_query(gl_q, q, BLOCK_Q, T_CHUNK, wt.dtype)
        B = block_prox_padded(glq, qq, glwt, wt, interpret=interpret)
    with jax.named_scope("select"):
        return jax.lax.top_k(B[:gl_q.shape[0], :n_ref], k)


@functools.partial(jax.jit, static_argnames=("mesh", "total_leaves",
                                             "data_axis", "model_axis"))
def _sharded_product(gl, q, w, V, mesh: Mesh, total_leaves: int,
                     data_axis: str, model_axis: str) -> jax.Array:
    def local(gl_s, q_s, w_s, V_s):
        # shapes: gl_s (n/dp, T/mp), V_s (n/dp, C)
        # local leaf ids are globally unique per model shard -> bucket into a
        # full-size table to keep indexing static, then psum over data only.
        # One tree per step, as in _swlc_product (bounded scatter rows).
        def tree(t):
            col = functools.partial(jax.lax.dynamic_index_in_dim, index=t,
                                    axis=1, keepdims=False)
            return jax.ops.segment_sum(col(w_s)[:, None] * V_s, col(gl_s),
                                       num_segments=total_leaves)

        # tree 0 seeds the carry, so it has the per-shard type of the body
        with jax.named_scope("bucket"):
            s = jax.lax.fori_loop(1, gl_s.shape[1],
                                  lambda t, s: s + tree(t), tree(0))
            s = jax.lax.psum(s, data_axis)                 # (L, C)
        with jax.named_scope("gather"):
            out = (q_s[:, :, None] * s[gl_s]).sum(axis=1)  # (n/dp, C)
            return jax.lax.psum(out, model_axis)

    spec_nt = P(data_axis, model_axis)
    spec_nc = P(data_axis, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec_nt, spec_nt, spec_nt, spec_nc),
                         out_specs=spec_nc)(gl, q, w, V)


def sharded_swlc_matmat(mesh: Mesh, gl: jax.Array, q: jax.Array, w: jax.Array,
                        V: jax.Array, total_leaves: int,
                        data_axis: str = "data",
                        model_axis: str = "model") -> jax.Array:
    """P V on a (data, model) mesh: samples sharded over `data`, trees over
    `model`.  Leaf ids are tree-major, so each model shard's buckets are a
    private contiguous range — the bucket stage needs **no** collective; the
    bucket table is psum'ed over `data` and the per-tree partial outputs are
    psum'ed over `model`.
    """
    # observed into the same engine_op_seconds family the profiled engine
    # wrapper uses, so sharded calls show up in /metrics and snapshots
    # instead of bypassing observability (block_until_ready keeps the
    # timing honest under async dispatch).
    import time as _time

    from ..obs.metrics import global_registry
    reg = global_registry()
    t0 = _time.perf_counter()
    out = _sharded_product(gl, q, w, V, mesh, total_leaves, data_axis,
                           model_axis)
    out.block_until_ready()
    dt = _time.perf_counter() - t0
    reg.histogram("engine_op_seconds", "engine op latency (s)",
                  labels=("op", "backend", "tier")).labels(
        op="sharded_matmat", backend="jax", tier="").observe(dt)
    reg.counter("engine_op_calls_total", "engine op invocations",
                labels=("op", "backend", "tier")).labels(
        op="sharded_matmat", backend="jax", tier="").inc()
    return out
