"""Pallas TPU kernels: split histograms via one-hot MXU matmuls.

TPU adaptation of the CPU ``np.add.at`` histogram (DESIGN.md §3): random
scatter is replaced by a dense contraction

    H[(node,class), (feature,bin)] = Σ_i  A[i,(node,class)] · B[i,(feature,bin)]

with A = w-weighted one-hot of (node, class) and B = one-hot of each
feature's bin code.  Per sample tile this is a (nodes·C × tile) × (tile ×
D·bins) matmul — exactly MXU shape.  The grid walks sample tiles and
accumulates into the same output block (sequential TPU grid ⇒ safe
read-modify-write).

Layout: every operand is lane-dense.  Samples run along lanes — the codes
arrive transposed as (D, tile), node/class ids and payloads as (1, tile) /
(K, tile) rows — so both one-hots are built transposed, Aᵀ (nodes·C, tile)
and Bᵀ (D·bins, tile), from 2-D iota compares, and the contraction is one
``dot_general`` over the tile axis.  No 3-D intermediate exists, so the
VMEM a call needs is a sum of padded 2-D buffers (``hist_vmem_bytes``).

Two kernel variants share that structure:

  ``histogram_pallas``  per-(node, class) weight sums — classification,
  ``moments_pallas``    per-node (Σw, Σwy, Σwy²)-style payload sums —
                        regression / gradient boosting; the payload matrix
                        ``wm`` carries one column per accumulated moment.

Both entry points *enforce* ``vmem_budget`` — they raise instead of
emitting a block that cannot fit, and hand the same number to the compiler
as its scoped-VMEM limit; the ``ops.py`` wrapper chunks nodes AND features
so callers never have to think about it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["histogram_pallas", "moments_pallas", "hist_vmem_bytes",
           "DEFAULT_VMEM_BUDGET"]

# Per-core VMEM we allow one histogram call to occupy.  v5e's default
# scoped limit is 16 MiB per kernel; keep headroom below it.
DEFAULT_VMEM_BUDGET = 12 << 20

_DOT_TN = (((1,), (1,)), ((), ()))     # contract the tile (lane) axis


def _padded(rows: int, cols: int) -> int:
    """f32/int32 elements of a 2-D VMEM buffer in (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128)


def hist_vmem_bytes(tile: int, d: int, n_nodes: int, n_bins: int,
                    n_channels: int) -> int:
    """Upper bound on the VMEM one kernel invocation allocates, in bytes.

    Counts, in (8, 128)-padded 4-byte tiles: the (rows, d·bins) output
    block double-buffered plus the matmul result and the accumulate
    temporary; the Bᵀ one-hot with its per-feature compare parts; the Aᵀ
    one-hot with its iota and mask; and the double-buffered input tiles
    (codes, ids and payload rows).  ``rows`` is nodes·channels for either
    kernel.  ``tests/test_tpu_compile.py`` compiles both kernels for v5e
    with this number as the compiler's scoped-VMEM limit.
    """
    rows = n_nodes * n_channels
    acc = _padded(rows, d * n_bins)
    b = _padded(d * n_bins, tile)
    a = _padded(rows, tile)
    inputs = _padded(d, tile) + _padded(n_channels, tile) + 2 * _padded(1, tile)
    return 4 * (4 * acc + 2 * b + 3 * a + 2 * inputs)


def _check_vmem(tile: int, d: int, n_nodes: int, n_bins: int,
                n_channels: int, vmem_budget: int) -> None:
    need = hist_vmem_bytes(tile, d, n_nodes, n_bins, n_channels)
    if need > vmem_budget:
        raise ValueError(
            f"histogram kernel block needs ~{need / 2**20:.1f} MiB VMEM "
            f"(tile={tile}, d={d}, nodes={n_nodes}, bins={n_bins}, "
            f"channels={n_channels}) > budget {vmem_budget / 2**20:.1f} MiB; "
            "chunk nodes and/or features via kernels.histogram.ops.histogram "
            "(it sizes blocks to fit), or raise vmem_budget explicitly")


def _bin_onehot_t(xbt: jax.Array, n_bins: int) -> jax.Array:
    """(D, tile) codes -> (D·bins, tile) f32 transposed bin one-hot."""
    d, tile = xbt.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_bins, tile), 0)
    return jnp.concatenate(
        [(iota == xbt[f:f + 1, :]).astype(jnp.float32) for f in range(d)],
        axis=0)


def _onehot_t(ids: jax.Array, n: int) -> jax.Array:
    """(1, tile) ids -> (n, tile) f32 transposed one-hot."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, ids.shape[1]), 0)
    return (iota == ids).astype(jnp.float32)


def _accumulate(out_ref, at: jax.Array, bt: jax.Array) -> None:
    partial = jax.lax.dot_general(at, bt, _DOT_TN,
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


def _hist_kernel(xbt_ref, node_ref, y_ref, w_ref, out_ref, *,
                 n_nodes: int, n_bins: int, n_classes: int):
    # xbt (D, tile); node / y / w (1, tile)
    nc = node_ref[...] * n_classes + y_ref[...]
    at = _onehot_t(nc, n_nodes * n_classes) * w_ref[...]   # (nodes·C, tile)
    _accumulate(out_ref, at, _bin_onehot_t(xbt_ref[...], n_bins))


def _moments_kernel(xbt_ref, node_ref, wmt_ref, out_ref, *,
                    n_nodes: int, n_bins: int, n_mom: int):
    # xbt (D, tile); node (1, tile); wmt (K, tile) payload rows
    oh = _onehot_t(node_ref[...], n_nodes)                  # (nodes, tile)
    wmt = wmt_ref[...]
    at = jnp.concatenate([oh * wmt[k:k + 1, :] for k in range(n_mom)],
                         axis=0)                            # (K·nodes, tile)
    _accumulate(out_ref, at, _bin_onehot_t(xbt_ref[...], n_bins))


def _call(kernel, tile: int, rows: int, n_bins: int, xb, node, extra,
          interpret: bool, vmem_budget: int):
    """Shared pallas_call: pad samples to the tile, transpose to lane-dense
    rows, walk the sample tiles into one resident (rows, D·bins) block."""
    n, d = xb.shape
    n_pad = -(-n // tile) * tile
    pad = n_pad - n                    # zero weight -> no contribution
    xbt = jnp.pad(xb.astype(jnp.int32), ((0, pad), (0, 0))).T
    node = jnp.pad(node.astype(jnp.int32), (0, pad))[None, :]
    extra = [jnp.pad(e, ((0, 0), (0, pad))) for e in extra]
    return pl.pallas_call(
        kernel,
        grid=(n_pad // tile,),
        in_specs=[pl.BlockSpec((d, tile), lambda i: (0, i)),
                  pl.BlockSpec((1, tile), lambda i: (0, i))]
        + [pl.BlockSpec((e.shape[0], tile), lambda i: (0, i)) for e in extra],
        out_specs=pl.BlockSpec((rows, d * n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d * n_bins), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(vmem_budget)),
        interpret=interpret,
    )(xbt, node, *extra)


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "n_bins", "n_classes", "tile", "interpret", "vmem_budget"))
def histogram_pallas(xb: jax.Array, node: jax.Array, y: jax.Array,
                     w: jax.Array, n_nodes: int, n_bins: int, n_classes: int,
                     tile: int = 512, interpret: bool = False,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET) -> jax.Array:
    """Returns (n_nodes, D, n_bins, n_classes) float32 class histograms."""
    d = xb.shape[1]
    _check_vmem(tile, d, n_nodes, n_bins, n_classes, vmem_budget)
    kernel = functools.partial(_hist_kernel, n_nodes=n_nodes, n_bins=n_bins,
                               n_classes=n_classes)
    out = _call(kernel, tile, n_nodes * n_classes, n_bins, xb, node,
                [y.astype(jnp.int32)[None, :],
                 w.astype(jnp.float32)[None, :]], interpret, vmem_budget)
    return out.reshape(n_nodes, n_classes, d, n_bins).transpose(0, 2, 3, 1)


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "n_bins", "n_mom", "tile", "interpret", "vmem_budget"))
def moments_pallas(xb: jax.Array, node: jax.Array, wm: jax.Array,
                   n_nodes: int, n_bins: int, n_mom: int,
                   tile: int = 512, interpret: bool = False,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> jax.Array:
    """Returns (n_nodes, D, n_bins, n_mom) float32 payload-sum histograms.

    ``wm`` is (N, n_mom): one column per accumulated moment — the trainer
    passes (w, w·y, w·y²) so regression/GBT split scoring gets its
    (Σw, Σwy, Σwy²) channels from the same MXU contraction.  Output rows
    are moment-major inside the kernel and transposed back here.
    """
    d = xb.shape[1]
    _check_vmem(tile, d, n_nodes, n_bins, n_mom, vmem_budget)
    kernel = functools.partial(_moments_kernel, n_nodes=n_nodes,
                               n_bins=n_bins, n_mom=n_mom)
    out = _call(kernel, tile, n_nodes * n_mom, n_bins, xb, node,
                [wm.astype(jnp.float32).T], interpret, vmem_budget)
    return out.reshape(n_mom, n_nodes, d, n_bins).transpose(1, 2, 3, 0)
