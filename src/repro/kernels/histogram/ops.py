"""Host-side wrapper for the histogram kernel family.

Responsibilities (all fixed here so the kernels stay simple):

  * **Interpret gating.** Interpret mode on the CPU backend, compiled
    kernels everywhere else (``kernels.interpret_mode``); callers can
    override with ``interpret=``.  A kernel the accelerator's compiler
    refuses raises — nothing falls back.  The kernels are 32-bit, so they
    are traced with x64 off whatever the caller's scope.
  * **Node chunking with pre-partitioned sample ranges.** Above
    ``max_node_chunk`` the samples are stably sorted by node once and each
    chunk's kernel call sees ONLY its own sample range.
  * **Bucketed shapes.** Each call's sample count is zero-weight padded to
    a power of two (at least one tile) and its node count to a power of
    two, so a fit compiles log-many kernel variants, not one per chunk.
  * **Feature chunking.** The kernel emits one resident
    ``(nodes·C, d·bins)`` accumulator block; wide ``d·bins`` is split into
    feature blocks sized so the whole invocation fits ``vmem_budget``
    (the kernels assert the same budget — nothing can slip through).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import interpret_mode
from .histogram import (DEFAULT_VMEM_BUDGET, hist_vmem_bytes,
                        histogram_pallas, moments_pallas)
from .ref import histogram_ref, moments_ref

__all__ = ["histogram", "moments", "resolve_interpret"]


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve the interpret flag: caller override wins, else CPU-only."""
    return interpret_mode() if interpret is None else bool(interpret)


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _feature_blocks(d: int, tile: int, n_nodes: int, n_bins: int,
                    n_channels: int, vmem_budget: int) -> int:
    """Largest feature-block width whose kernel call fits ``vmem_budget``."""
    db = d
    while db > 1 and hist_vmem_bytes(tile, db, n_nodes, n_bins,
                                     n_channels) > vmem_budget:
        db = (db + 1) // 2
    return max(1, db)


def _node_chunks(node: np.ndarray, n_nodes: int, max_node_chunk: int):
    """Stable-sort samples by node once; return (c0, c1, sample rows) per
    chunk, each chunk owning exactly its own samples."""
    if n_nodes <= max_node_chunk:
        return [(0, n_nodes, None)]
    order = np.argsort(node, kind="stable")
    node_sorted = node[order]
    starts = np.arange(0, n_nodes, max_node_chunk)
    ends = np.minimum(starts + max_node_chunk, n_nodes)
    i0 = np.searchsorted(node_sorted, starts, side="left")
    i1 = np.searchsorted(node_sorted, ends, side="left")
    return [(int(c0), int(c1), order[a:b])
            for c0, c1, a, b in zip(starts, ends, i0, i1)]


def _run(kernel, xb, node, cols, n_nodes: int, n_bins: int, n_ch: int,
         tile: int, max_node_chunk: int, interpret: bool,
         vmem_budget: int) -> jax.Array:
    """Shared chunking driver.  ``cols`` are the per-sample payload arrays
    handed to ``kernel`` after (codes, node); padded samples get zero
    payload and so contribute nothing."""
    n, d = xb.shape
    outs = []
    for c0, c1, sel in _node_chunks(np.asarray(node), n_nodes,
                                    max_node_chunk):
        nc = c1 - c0
        m = n if sel is None else len(sel)
        if m == 0:
            outs.append(jnp.zeros((nc, d, n_bins, n_ch), jnp.float32))
            continue
        mp, ncp = max(tile, _pow2(m)), _pow2(nc)
        idx = np.zeros(mp, np.int32)
        idx[:m] = np.arange(m) if sel is None else sel
        live = np.zeros((mp,) + (1,) * (cols[-1].ndim - 1), np.float32)
        live[:m] = 1.0
        with jax.enable_x64(False):
            ix = jnp.asarray(idx)
            xb_c = xb[ix]
            node_c = jnp.where(live.reshape(mp) > 0, node[ix] - c0, 0)
            cols_c = [c[ix] for c in cols[:-1]] + [cols[-1][ix] * live]
            db = _feature_blocks(d, tile, ncp, n_bins, n_ch, vmem_budget)
            parts = [kernel(xb_c[:, f0:f0 + db], node_c, *cols_c, ncp,
                            n_bins, n_ch, tile=tile, interpret=interpret,
                            vmem_budget=vmem_budget)
                     for f0 in range(0, d, db)]
            h = parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts, axis=1)
        outs.append(h[:nc])
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def histogram(xb, node, y, w, n_nodes: int, n_bins: int, n_classes: int,
              tile: int = 512, use_pallas: bool = True,
              max_node_chunk: int = 64, interpret: Optional[bool] = None,
              vmem_budget: int = DEFAULT_VMEM_BUDGET) -> jax.Array:
    """(n_nodes, D, n_bins, C) float32 class histograms, chunked to fit VMEM."""
    xb = jnp.asarray(xb, jnp.int32)
    node = jnp.asarray(node, jnp.int32)
    y = jnp.asarray(y, jnp.int32)
    w = jnp.asarray(w, jnp.float32)
    if not use_pallas:
        return histogram_ref(xb, node, y, w, n_nodes, n_bins, n_classes)
    return _run(histogram_pallas, xb, node, [y, w], n_nodes, n_bins,
                n_classes, tile, max_node_chunk, resolve_interpret(interpret),
                vmem_budget)


def moments(xb, node, wm, n_nodes: int, n_bins: int,
            tile: int = 512, use_pallas: bool = True,
            max_node_chunk: int = 64, interpret: Optional[bool] = None,
            vmem_budget: int = DEFAULT_VMEM_BUDGET) -> jax.Array:
    """(n_nodes, D, n_bins, K) float32 payload-sum histograms.

    ``wm`` is (N, K) payload columns — the trainer passes (w, w·y, w·y²)
    so regression split scoring gets its moment channels on-device.
    """
    xb = jnp.asarray(xb, jnp.int32)
    node = jnp.asarray(node, jnp.int32)
    wm = jnp.asarray(wm, jnp.float32)
    if not use_pallas:
        return moments_ref(xb, node, wm, n_nodes, n_bins, wm.shape[1])
    return _run(moments_pallas, xb, node, [wm], n_nodes, n_bins,
                wm.shape[1], tile, max_node_chunk,
                resolve_interpret(interpret), vmem_budget)
