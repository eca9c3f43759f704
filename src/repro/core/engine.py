"""Device-resident proximity engine with backend dispatch.

``ProximityEngine`` is built **once** per fitted kernel and owns every array
the hot paths need:

- dense ``(gl, q, w)`` factor arrays (the SWLC weights of Def 3.1),
- the CSR leaf maps ``Q``/``W`` (Lemma 3.4 factors, scipy path),
- the stacked global leaf-value table of the backing forest,
- an LRU cache of out-of-sample query states, so repeated ``predict(X)`` /
  ``query_map(X)`` calls on the same batch never re-route or rebuild CSR.

Backends
--------
``scipy``   CSR sparse·sparseᵀ products (the paper's reference path).
``jax``     segment-sum factorization (``core.jax_ops``) — O(N T) with
            static shapes; runs under x64 when the engine dtype is float64
            so results match scipy to ~1e-12.
``pallas``  same segment-sum matvec/matmat, but dense block queries and
            top-k go through the ``block_prox`` Pallas kernel (interpret
            mode on the CPU; compiled, it computes in float32 —
            ``block_dtype`` says which).
``native``  the lazily-compiled C kernels of ``forest._native`` (the same
            ``.so`` as the native router): bucket/gather matmat and dense
            collision blocks, accumulating in float64 like scipy.  Needs a
            host compiler — gate on ``forest._native.available()``.

Serving note: the bucket table S = Wᵀ V of every factored product depends
only on the reference side, so for narrow V it is LRU-cached by V content
on every backend: a host array on scipy and native, a device array on jax
and pallas (built there by ``jax_ops.swlc_bucket`` and never copied back).
A serving loop calling ``predict(X=batch)`` every tick with the same
labels pays the O(N T C) bucket once and only the O(n_batch T C)
query-side gather per tick; on jax / pallas it uploads only the batch's
(gl, q).

No path in this module iterates over trees in Python.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import LinearOperator

from ..forest import _native
from ..obs.trace import span
from .factorization import (full_kernel, kernel_block, kernel_matvec_operator,
                            prefix_leaf_contraction, topk_neighbors)
from .leafmap import build_leaf_map

__all__ = ["ProximityEngine", "PrefixProximityEngine", "QueryState",
           "ENGINE_BACKENDS", "prediction_margin"]

ENGINE_BACKENDS = ("scipy", "jax", "pallas", "native")


@dataclasses.dataclass
class QueryState:
    """Everything needed to use a sample batch as the query side of P."""

    gl: np.ndarray               # (Nq, T) int64 global leaf ids
    q: np.ndarray                # (Nq, T) float query weights
    Q: sp.csr_matrix             # (Nq, L) CSR leaf map


def _x64_scope(enabled: bool):
    import contextlib

    import jax
    return jax.enable_x64(True) if enabled else contextlib.nullcontext()


def _stage(*arrays: np.ndarray) -> tuple:
    """The host arrays on the device, under one ``engine.upload`` span whose
    ``bytes`` stat is what is staged: each array in the dtype JAX gives it
    (64-bit stays 64-bit under the caller's x64 scope only).

    The span times the host's part of the staging; the transfers finish
    on the runtime's threads, and the device waits for them inside the
    ``engine.fetch`` that follows."""
    import jax
    import jax.numpy as jnp
    n = sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
            for a in arrays)
    with span("engine.upload", bytes=int(n)):
        return tuple(jnp.asarray(a) for a in arrays)


def _fetch(*outs) -> tuple:
    """Device results as host arrays, under one ``engine.fetch`` span: the
    wait for the device and the copy back."""
    with span("engine.fetch"):
        return tuple(np.asarray(o) for o in outs)


_DEVICE_BACKENDS = ("jax", "pallas")


def _count_path(op: str, backend: str, cutover: bool = False) -> None:
    """Count where an engine op computed its proximities, in the global
    ``engine_op_path_total{op,backend,path}`` family: ``device`` (jax /
    pallas arrays), ``host`` (scipy / native) or ``host_cutover`` (a device
    backend's training-set op above ``_SPARSE_TRAIN_CUTOVER``, run as
    scipy CSR) — so a device run can tell its device work from host work."""
    from ..obs.metrics import global_registry
    on_device = backend in _DEVICE_BACKENDS
    path = ("host_cutover" if on_device else "host") if cutover else \
        ("device" if on_device else "host")
    global_registry().counter(
        "engine_op_path_total", "engine ops by where they computed",
        labels=("op", "backend", "path")).labels(
        op=op, backend=backend, path=path).inc()


class ProximityEngine:
    """Serves matvec / matmat / predict / topk / kernel_block for P = Q Wᵀ."""

    def __init__(self, ctx, assignment, forest=None, backend: str = "scipy",
                 dtype=np.float64, oos_cache_size: int = 8,
                 ref_cache_size: int = 16,
                 factors: Optional[Tuple[np.ndarray,
                                         Optional[np.ndarray]]] = None,
                 memory_budget_bytes: Optional[int] = None,
                 factor_scratch_dir: Optional[str] = None):
        if backend not in ENGINE_BACKENDS:
            raise ValueError(f"unknown engine backend {backend!r}; "
                             f"have {ENGINE_BACKENDS}")
        if backend == "native" and not _native.available():
            raise RuntimeError(
                "engine backend 'native' needs a host C compiler (cc/gcc) "
                "and REPRO_DISABLE_NATIVE unset; gate on "
                "forest._native.available() or use backend='scipy'")
        self.ctx = ctx
        self.assignment = assignment
        self.forest = forest
        self.backend = backend
        self.dtype = np.dtype(dtype)
        self.total_leaves = int(ctx.total_leaves)
        self.memory_budget_bytes = None if memory_budget_bytes is None \
            else int(memory_budget_bytes)
        self._factor_scratch_dir = factor_scratch_dir

        # dense factors (device-ready; one build, reused by every op).
        # ``factors=(q, w)`` injects precomputed weight arrays — the
        # snapshot warm-start path, which must not re-run the assignment's
        # (possibly expensive) weight computation.
        self.gl = ctx.global_leaves()                        # (N, T) int64
        if factors is not None:
            q, w = factors
            self.q = np.ascontiguousarray(q, dtype=self.dtype)
            self.w = self.q if (assignment.symmetric or w is None) else \
                np.ascontiguousarray(w, dtype=self.dtype)
        else:
            self.q = np.ascontiguousarray(
                assignment.query_weights(ctx.leaves), dtype=self.dtype)
            if assignment.symmetric:
                self.w = self.q
            else:
                self.w = np.ascontiguousarray(
                    assignment.reference_weights(ctx.leaves),
                    dtype=self.dtype)

        # CSR factors (scipy path + memory accounting).  Under a memory
        # budget the streamed builder bounds the (chunk, T) build transient
        # and spills indices/data to scratch memmaps when they alone would
        # eat the budget — bit-identical output either way.
        self.Q = self._build_factor(self.q)
        self.W = self.Q if assignment.symmetric else self._build_factor(self.w)

        # stacked global leaf-value table (forest payloads, tree-major)
        self.leaf_values = None if forest is None else \
            getattr(forest, "leaf_values_", None)

        self._init_runtime_state(oos_cache_size=oos_cache_size,
                                 ref_cache_size=ref_cache_size)

    def _build_factor(self, weights: np.ndarray) -> sp.csr_matrix:
        budget = self.memory_budget_bytes
        if budget is None:
            return build_leaf_map(self.gl, weights, self.total_leaves,
                                  self.dtype)
        from .factorization import streamed_leaf_map
        T = self.gl.shape[1]
        # ~32 bytes of build transient per (row, tree) cell
        row_chunk = max(1024, budget // max(32 * T, 1))
        return streamed_leaf_map(self.gl, weights, self.total_leaves,
                                 self.dtype, row_chunk=row_chunk,
                                 memmap_threshold_bytes=budget,
                                 scratch_dir=self._factor_scratch_dir)

    def _init_runtime_state(self, oos_cache=None, oos_cache_size: int = 8,
                            ref_cache_size: int = 16,
                            oos_lock: Optional[threading.Lock] = None) -> None:
        """Per-engine mutable state; the single place both the primary
        constructor and factor-slicing views (CompressedProximityEngine)
        initialize it, so new runtime attributes cannot silently go missing
        on one of them.  Expects the factor attributes (gl/q/w/Q/W, dtype,
        backend, …) to be set already."""
        # factor-slicing views never pass the budget through __init__
        self.memory_budget_bytes = getattr(self, "memory_budget_bytes", None)
        self._train_state = QueryState(gl=self.gl, q=self.q, Q=self.Q)
        # routed OOS query states; a view may share its parent's cache (one
        # routed batch serves both engines).  The tiered server touches the
        # cache from one worker thread per tier, so cache bookkeeping is
        # guarded by a lock — which must be the SAME lock object wherever
        # the cache dict itself is shared (two locks guarding one dict
        # protect nothing).
        self._oos_cache: "OrderedDict[str, QueryState]" = \
            OrderedDict() if oos_cache is None else oos_cache
        self._oos_cache_size = oos_cache_size
        self._qs_lock = threading.Lock() if oos_lock is None else oos_lock
        self.qs_cache_hits = 0
        self.qs_cache_misses = 0
        self.ref_cache_hits = 0
        self.ref_cache_misses = 0
        self._use_x64 = self.dtype == np.float64
        # dtype of the dense block ops (kernel_block, topk, OOS squared row
        # sums) on pallas: the compiled block_prox kernel is float32-only,
        # so off the CPU the engine asks it for float32 and upcasts the
        # result to ``dtype``.  Every other op computes in ``dtype``.
        from ..kernels import interpret_mode
        self.block_dtype = np.dtype(np.float32) if (
            self.backend == "pallas" and not interpret_mode()) else self.dtype
        self._train_row_sums: Optional[np.ndarray] = None
        self.last_matmat_path: Optional[str] = None   # 'sharded' | 'segment'
        # reference bucket tables S = Wᵀ V (serving), LRU of key ->
        # (keepalive V | None, S); S is a device array on jax / pallas.
        # Sized above the number of distinct fixed tables a mixed serving
        # tick touches (labels, ones, propagation field, Nyström basis,
        # per-class masks) so rotating inserts from iterative solvers
        # cannot thrash the hot entries; additionally bounded in bytes so
        # huge-L engines cannot pin hundreds of MB of dead tables, on the
        # host or on the device.  ref_cache_hits / ref_cache_misses count
        # the lookups of cacheable V.
        self._ref_cache: "OrderedDict[object, tuple]" = OrderedDict()
        self._ref_cache_size = ref_cache_size
        self._ref_cache_bytes = 0
        self._ref_cache_byte_budget = 1 << 27          # 128 MiB of tables
        # label tables for predict, memoized by label-array identity (small
        # LRU; cached arrays are treated as immutable)
        self._label_cache: "OrderedDict[object, tuple]" = OrderedDict()
        self._app_cache: dict = {}    # application-level per-engine caches
        # reference side of the pallas block kernels, staged once
        # (``_block_factors``): a device (gl, w) pair in the kernel's layout
        self._block_ref: Optional[tuple] = None

    # ---------------- query-state management ----------------
    @staticmethod
    def _batch_key(X: np.ndarray) -> str:
        X = np.ascontiguousarray(X)
        h = hashlib.sha1()
        h.update(str(X.shape).encode())
        h.update(str(X.dtype).encode())
        h.update(X.tobytes())
        return h.hexdigest()

    def query_state(self, X: Optional[np.ndarray] = None) -> QueryState:
        """Training state (X=None) or a cached OOS state for a new batch."""
        if X is None:
            return self._train_state
        with span("engine.batch_key"):
            key = self._batch_key(np.asarray(X))
        hit = self._qs_cache_get(key)
        if hit is not None:
            return hit
        assert self.forest is not None, "OOS queries need the backing forest"
        with span("engine.apply"):
            leaves = self.forest.apply(X)
        gl = leaves.astype(np.int64) + self.ctx.leaf_offset[None, :]
        with span("engine.weights"):
            q = np.ascontiguousarray(
                self.assignment.oos_query_weights(leaves), dtype=self.dtype)
        with span("engine.leaf_map"):
            Q = build_leaf_map(gl, q, self.total_leaves, self.dtype)
        return self._qs_cache_put(key, QueryState(gl=gl, q=q, Q=Q))

    def _qs_cache_get(self, key: str) -> Optional[QueryState]:
        with self._qs_lock:
            hit = self._oos_cache.get(key)
            if hit is not None:
                self._oos_cache.move_to_end(key)
                self.qs_cache_hits += 1
            else:
                self.qs_cache_misses += 1
            return hit

    def _qs_cache_put(self, key: str, state: QueryState) -> QueryState:
        # build happens outside the lock — two threads racing on the same
        # new batch duplicate work, never corrupt the dict
        with self._qs_lock:
            self._oos_cache[key] = state
            while len(self._oos_cache) > self._oos_cache_size:
                self._oos_cache.popitem(last=False)
        return state

    # ---------------- core products ----------------
    def matvec(self, v: np.ndarray, X: Optional[np.ndarray] = None,
               col_mask: Optional[np.ndarray] = None,
               normalized: bool = False) -> np.ndarray:
        return self.matmat(np.asarray(v)[:, None], X=X, col_mask=col_mask,
                           normalized=normalized)[:, 0]

    def matmat(self, V: np.ndarray, X: Optional[np.ndarray] = None,
               col_mask: Optional[np.ndarray] = None,
               normalized: bool = False) -> np.ndarray:
        """(P V) where P's rows are the train (X=None) or OOS query batch.

        ``col_mask`` (N_ref,) restricts the reference side:
        Σ_j m_j P(i,j) V[j] — since P V = Q (Wᵀ V), the mask folds into V as
        Q (Wᵀ (m ⊙ V)) on every backend (the class-masked matmat primitive).
        ``normalized`` divides each output row by the *unmasked* kernel row
        sum Σ_j P(i,j), i.e. applies D⁻¹ P (the label-propagation operator).
        """
        V = np.asarray(V, dtype=self.dtype)
        if col_mask is not None:
            V = V * np.asarray(col_mask, dtype=self.dtype)[:, None]
        qs = self.query_state(X)
        cb = self._col_chunk(V.shape[1])
        if cb < V.shape[1]:
            # bound the (total_leaves, C) bucket table of P V = Q (Wᵀ V):
            # columns are independent, so block splitting is bit-identical
            first = self._dispatch_matmat(
                qs, np.ascontiguousarray(V[:, :cb]), ref_key=False)
            out = np.empty((first.shape[0], V.shape[1]), dtype=first.dtype)
            out[:, :cb] = first
            del first
            for j0 in range(cb, V.shape[1], cb):
                j1 = min(j0 + cb, V.shape[1])
                out[:, j0:j1] = self._dispatch_matmat(
                    qs, np.ascontiguousarray(V[:, j0:j1]), ref_key=False)
        else:
            out = self._dispatch_matmat(qs, V)
        if normalized:
            d = self.row_sums(X=X)
            out = out / np.maximum(d, np.finfo(self.dtype).tiny)[:, None]
        return out

    def _dispatch_matmat(self, qs: QueryState, V: np.ndarray,
                         ref_key=None) -> np.ndarray:
        """Backend dispatch for (P V) on an already-resolved query state."""
        _count_path("matmat", self.backend)
        if self.backend in _DEVICE_BACKENDS:
            return self._segment_matmat(qs, V, ref_key)
        S = self._ref_table(V, key=ref_key)
        if self.backend == "scipy":
            return np.asarray(qs.Q @ S)
        out = _native.prox_gather_native(qs.gl, qs.q, S)
        return out.astype(self.dtype, copy=False)

    def _ref_table(self, V: np.ndarray, key=None):
        """Reference bucket table S = Wᵀ V of the factored product
        P V = Q (Wᵀ V) — the half that does not depend on the query rows:
        a host array on scipy / native; on jax / pallas a device array of
        (total_leaves + 1) rows (``jax_ops.swlc_bucket``), which stays on
        the device, so a cached table costs a call no transfer at all.

        Narrow V (≤ 32 columns: labels, class scores, Nyström bases) is
        LRU-cached, so a serving loop re-applying the same V every tick pays
        the O(N_ref) bucket pass once and only the O(n_query) gather per
        tick.  Callers whose V is content-stable across distinct array
        objects (label tables, the ones vector) pass an explicit ``key``;
        anonymous V is keyed by **object identity** (the array is held
        alive in the entry so its id cannot be recycled while cached — no
        per-call content hash anywhere, and iterative solvers whose V
        changes every call just rotate through the LRU without hashing).
        Cached arrays are treated as immutable; mutate a cached V in place
        and you get the stale table.  Wide V bypasses the cache (an (L, C)
        table would dwarf the factors), and total cached bytes, host or
        device, are bounded.  Each lookup counts as ``hit``, ``miss`` or
        ``uncached`` in ``engine_ref_table_total{backend,result}``.
        """
        keepalive = None
        if key is False:        # budget-chunked slice: never worth caching
            key = None
        elif key is None and V.shape[1] <= 32:
            key = ("id", id(V))
            keepalive = V
        if key is not None:
            hit = self._ref_cache.get(key)
            if hit is not None:
                self._ref_cache.move_to_end(key)
                self.ref_cache_hits += 1
                self._count_ref_table("hit")
                return hit[1]
        with span("engine.ref_table"):
            S = self._build_ref_table(V)
        if key is None:
            self._count_ref_table("uncached")
            return S
        self.ref_cache_misses += 1
        self._count_ref_table("miss")
        self._ref_cache[key] = (keepalive, S)
        self._ref_cache_bytes += S.nbytes
        while len(self._ref_cache) > self._ref_cache_size or \
                self._ref_cache_bytes > self._ref_cache_byte_budget:
            _, (_, old) = self._ref_cache.popitem(last=False)
            self._ref_cache_bytes -= old.nbytes
        return S

    def _build_ref_table(self, V: np.ndarray):
        if self.backend == "native":
            return _native.prox_bucket_native(self.gl, self.w, V,
                                              self.total_leaves)
        if self.backend == "scipy":
            return np.asarray(self.W.T @ V)
        from . import jax_ops
        with _x64_scope(self._use_x64):
            # one tree per step: leaf ids are tree-major, so each step
            # scatters N_ref rows into its own tree's buckets.  A single
            # scatter of all N_ref·T rows costs the TPU compiler minutes.
            staged = _stage(self.gl, self.w, V)
            with span("engine.dispatch"):
                S = jax_ops.swlc_bucket(*staged, self.total_leaves,
                                        t_chunk=1)
            # drop the inputs now: only S stays on the device
            del staged
        return S

    def _count_ref_table(self, result: str) -> None:
        from ..obs.metrics import global_registry
        global_registry().counter(
            "engine_ref_table_total",
            "reference bucket table lookups by result",
            labels=("backend", "result")).labels(
            backend=self.backend, result=result).inc()

    def row_sums(self, X: Optional[np.ndarray] = None) -> np.ndarray:
        """Kernel row sums Σ_j P(i,j) = P·1 through the factors (the degree
        vector of the proximity graph); cached for the training state."""
        if X is None and self._train_row_sums is not None:
            return self._train_row_sums
        ones = np.ones((self.W.shape[0], 1), dtype=self.dtype)
        qs = self.query_state(X)
        # fixed V: a stable ref key keeps OOS row sums O(n_query) per call
        out = self._dispatch_matmat(qs, ones,
                                    ref_key=("ones", self.W.shape[0]))[:, 0]
        if X is None:
            self._train_row_sums = out
        return out

    def _segment_matmat(self, qs: QueryState, V: np.ndarray,
                        ref_key=None) -> np.ndarray:
        from . import jax_ops
        n_ref, T = self.gl.shape
        with _x64_scope(self._use_x64):
            if qs is self._train_state:
                mesh = jax_ops.default_mesh()
                if mesh is not None:
                    n_dev = mesh.devices.shape[0]
                    # rows padded to a multiple of the device count: leaf 0
                    # with zero weights and zero V adds nothing, and the
                    # padded output rows are sliced off
                    pad = (-n_ref) % n_dev
                    rows = ((0, pad), (0, 0))
                    gl_d, q_d, w_d = _stage(np.pad(self.gl, rows),
                                            np.pad(self.q, rows),
                                            np.pad(self.w, rows))
                    # wide V: split into column blocks so the per-device
                    # (N/devices, T, c) intermediate stays bounded
                    n_loc = (n_ref + pad) // n_dev
                    c = jax_ops.auto_c_chunk(n_loc, T, V.shape[1])
                    c = V.shape[1] if c is None else c
                    parts = []
                    for j0 in range(0, V.shape[1], c):
                        V_d, = _stage(np.pad(V[:, j0:j0 + c], rows))
                        with span("engine.dispatch"):
                            out = jax_ops.sharded_swlc_matmat(
                                mesh, gl_d, q_d, w_d, V_d, self.total_leaves)
                        del V_d
                        parts += _fetch(out)
                    self.last_matmat_path = "sharded"
                    return np.concatenate(parts, axis=1)[:n_ref]
            S = self._ref_table(V, key=ref_key)
            staged = _stage(qs.gl, qs.q)
            with span("engine.dispatch"):
                out = jax_ops.swlc_gather(*staged, S, t_chunk=1)
            # drop the inputs now, as inline arguments would be: their
            # device buffers then go as soon as the gather has read them.
            # Held through the _fetch below, they stalled about 1 call in
            # 70 by 1-2 s on a TPU v5e (cause not known)
            del staged, S
            self.last_matmat_path = "segment"
            return _fetch(out)[0]

    def operator(self) -> LinearOperator:
        if self.backend == "scipy":
            return kernel_matvec_operator(self.Q, self.W)
        return LinearOperator(
            (self.Q.shape[0], self.W.shape[0]),
            matvec=self.matvec, matmat=self.matmat,
            rmatvec=lambda v: np.asarray(self.W @ (self.Q.T @ v)),
            dtype=self.dtype)

    @staticmethod
    def _row_chunk(n_cols: int, budget: int = 1 << 25) -> int:
        """Rows per dense-block device call so the (rows, n_cols, t_chunk)
        collision intermediate stays within ~budget elements."""
        return max(1, budget // max(8 * n_cols, 1))

    def _op_row_chunk(self, n_cols: int) -> int:
        """`_row_chunk` honoring ``memory_budget_bytes``: the element budget
        shrinks to ~budget/8 bytes-per-element so dense op intermediates fit
        the configured ceiling (floor keeps chunks from degenerating)."""
        if self.memory_budget_bytes is None:
            return self._row_chunk(n_cols)
        elems = min(1 << 25, max(1 << 12, self.memory_budget_bytes // 8))
        return self._row_chunk(n_cols, budget=elems)

    def _col_chunk(self, n_cols: int) -> int:
        """Columns per matmat pass: the factored product materializes a
        dense (total_leaves, C) bucket table, which at out-of-core scale
        (millions of leaves) dwarfs every other working set — keep it
        within half the budget by splitting V's independent columns."""
        if self.memory_budget_bytes is None or n_cols <= 1:
            return n_cols
        per_col = 8 * max(self.total_leaves, 1)
        return max(1, min(n_cols, self.memory_budget_bytes // (2 * per_col)))

    def _budget_block(self, block: int) -> int:
        """Sparse-path row-block size honoring ``memory_budget_bytes``.

        A CSR product block holds ~16 bytes per nonzero; the expected
        nonzeros per product row scale with T × (mean reference rows per
        leaf), so cap the block where a quarter of the budget covers it.
        """
        if self.memory_budget_bytes is None:
            return block
        T = self.gl.shape[1]
        per_row = 16 * T * max(1, int(self.W.nnz) // max(self.total_leaves, 1))
        return max(256, min(block, self.memory_budget_bytes // (4 * per_row)))

    # Above this reference-set size, train-side (X=None) topk and squared
    # row sums drop to the sparse CSR path on every backend: those are
    # all-pairs batch jobs where CSR restricts work to colliding pairs,
    # while the dense block paths pay the full N·N_ref·T — they exist for
    # *small OOS query batches* on the serving path.
    _SPARSE_TRAIN_CUTOVER = 8192

    # ---------------- kernel views ----------------
    def full_kernel(self, diagonal: Optional[float] = None) -> sp.csr_matrix:
        return full_kernel(self.Q, self.W, diagonal=diagonal)

    def kernel_block(self, rows: Optional[np.ndarray] = None,
                     cols: Optional[np.ndarray] = None,
                     X_rows: Optional[np.ndarray] = None) -> np.ndarray:
        """Dense P[rows, cols] (rows may be an OOS batch via X_rows)."""
        qs = self.query_state(X_rows)
        if rows is None:
            rows = np.arange(qs.Q.shape[0])
        rows = np.asarray(rows)
        _count_path("kernel_block", self.backend)
        if self.backend == "scipy":
            return kernel_block(qs.Q, self.W, rows, cols)
        gl_q, q = qs.gl[rows], qs.q[rows]
        gl_w = self.gl if cols is None else self.gl[cols]
        w = self.w if cols is None else self.w[cols]
        if self.backend == "native":
            out = _native.prox_block_native(gl_q, q, gl_w, w)
            return out.astype(self.dtype, copy=False)
        if self.backend == "jax":
            from .jax_ops import swlc_block
            out = np.empty((len(rows), gl_w.shape[0]), dtype=self.dtype)
            step = self._op_row_chunk(gl_w.shape[0])
            with _x64_scope(self._use_x64):
                gl_w_d, w_d = _stage(gl_w, w)
                for i0 in range(0, len(rows), step):
                    gl_q_d, q_d = _stage(gl_q[i0:i0 + step],
                                         q[i0:i0 + step])
                    with span("engine.dispatch"):
                        blk = swlc_block(gl_q_d, q_d, gl_w_d, w_d)
                    del gl_q_d, q_d
                    out[i0:i0 + step] = _fetch(blk)[0]
            return out
        from ..kernels.block_prox.ops import block_prox
        # the wrapper stages its own inputs and dispatches the kernel: the
        # span's bytes are those of the host arrays it is handed
        handed = gl_q.nbytes + q.nbytes + gl_w.nbytes + w.nbytes
        with _x64_scope(self.block_dtype == np.float64):
            with span("engine.dispatch", bytes=int(handed)):
                out = block_prox(gl_q, q, gl_w, w, dtype=self.block_dtype)
        return _fetch(out)[0].astype(self.dtype, copy=False)

    def squared_row_sums(self, class_ids: Optional[np.ndarray] = None,
                         n_classes: Optional[int] = None,
                         X: Optional[np.ndarray] = None,
                         block: int = 4096) -> np.ndarray:
        """Σ_j P(i,j)² per query row — the outlier-score primitive.

        With ``class_ids`` (N_ref,) the sum is bucketed by reference class:
        out[i, c] = Σ_{j: class_ids[j]=c} P(i,j)², shape (Nq, n_classes).
        Streamed in row blocks (sparse on scipy, dense device blocks on
        jax/pallas) — never a full dense P.
        """
        qs = self.query_state(X)
        n = qs.Q.shape[0]
        if class_ids is not None:
            class_ids = np.asarray(class_ids, dtype=np.int64)
            if n_classes is None:
                n_classes = int(class_ids.max()) + 1
            out = np.zeros((n, n_classes), dtype=self.dtype)
        else:
            out = np.zeros(n, dtype=self.dtype)

        cutover = X is None and self.W.shape[0] > self._SPARSE_TRAIN_CUTOVER
        _count_path("squared_row_sums", self.backend, cutover)
        if self.backend == "scipy" or cutover:
            block = self._budget_block(block)
            WT = self.W.T.tocsc()
            for i0 in range(0, n, block):
                B = (qs.Q[i0:i0 + block] @ WT).tocsr()
                nb = B.shape[0]
                rows = np.repeat(np.arange(nb), np.diff(B.indptr))
                d2 = B.data ** 2
                if class_ids is None:
                    out[i0:i0 + nb] = np.bincount(rows, weights=d2,
                                                  minlength=nb)
                else:
                    comb = rows * n_classes + class_ids[B.indices]
                    out[i0:i0 + nb] = np.bincount(
                        comb, weights=d2,
                        minlength=nb * n_classes).reshape(nb, n_classes)
            return out

        onehot = None
        if class_ids is not None:
            onehot = np.zeros((self.W.shape[0], n_classes), dtype=self.dtype)
            onehot[np.arange(self.W.shape[0]), class_ids] = 1.0
        step = min(block, self._op_row_chunk(self.W.shape[0]))
        for i0 in range(0, n, step):
            rows = np.arange(i0, min(i0 + step, n))
            B = self.kernel_block(rows, X_rows=X)
            B2 = B * B
            out[rows] = B2.sum(axis=1) if onehot is None else B2 @ onehot
        return out

    # ---------------- downstream ----------------
    def _label_table(self, y: np.ndarray, n_classes: Optional[int]):
        """(Y, ref_key) for predict's P·Y: one-hot classes or stacked
        (target, ones) regression columns.

        Serving calls predict with the *same* label array every tick;
        memoizing on the array's identity (holding a reference, so the id
        cannot be recycled while cached) makes steady-state prediction prep
        O(1) instead of O(N_train) one-hot building + content hashing.
        Bounded LRU: callers that rebuild their label array per call rotate
        through it instead of growing it.  Cached label arrays are treated
        as immutable (mutate one in place and you get stale scores).
        """
        memo_key = (id(y), n_classes)
        hit = self._label_cache.get(memo_key)
        if hit is not None and hit[0] is y:
            self._label_cache.move_to_end(memo_key)
            return hit[1], hit[2]
        if n_classes is not None:
            Y = np.zeros((len(y), n_classes), dtype=self.dtype)
            Y[np.arange(len(y)), np.asarray(y).astype(np.int64)] = 1.0
        else:
            Y = np.stack([np.asarray(y, dtype=np.float64),
                          np.ones(len(y))], axis=1).astype(self.dtype)
        ref_key = ("labels", self._batch_key(Y))
        self._label_cache[memo_key] = (y, Y, ref_key)
        while len(self._label_cache) > 4:
            self._label_cache.popitem(last=False)
        return Y, ref_key

    def predict(self, y: np.ndarray, n_classes: Optional[int] = None,
                X: Optional[np.ndarray] = None,
                exclude_self: Optional[bool] = None) -> np.ndarray:
        """Proximity-weighted prediction scores (Appendix I) via P·Y."""
        if exclude_self is None:
            exclude_self = X is None
        if exclude_self and X is not None:
            # The self-term pairs query row i with training row i, which is
            # only meaningful for the training query state.
            raise ValueError("exclude_self is only defined for training-set "
                             "queries (X=None)")
        qs = self.query_state(X)
        Y, ref_key = self._label_table(y, n_classes)
        out = self._dispatch_matmat(qs, Y, ref_key=ref_key)
        if exclude_self:
            # own-row contribution: same gl on both sides -> Σ_t q_t w_t
            diag = (qs.q * self.w).sum(axis=1)
            out = out - diag[:, None] * Y
        if n_classes is not None:
            return out
        return out[:, 0] / np.maximum(out[:, 1], 1e-300)

    def topk(self, k: int = 10, X: Optional[np.ndarray] = None,
             block: int = 4096) -> Tuple[np.ndarray, np.ndarray]:
        """Per-query top-k proximities (values descending).

        On pallas the block kernel scores each row block against the
        reference factors staged once (``_block_factors``) and the top k
        are chosen on the device: a call uploads only its rows' (gl, q)
        and fetches (rows, k) values and indices."""
        qs = self.query_state(X)
        cutover = X is None and self.W.shape[0] > self._SPARSE_TRAIN_CUTOVER
        _count_path("topk", self.backend, cutover)
        if self.backend == "scipy" or cutover:
            return topk_neighbors(qs.Q, self.W, k,
                                  block=self._budget_block(block))
        n = qs.Q.shape[0]
        kk = min(k, self.W.shape[0])
        idx = np.zeros((n, k), dtype=np.int64)
        val = np.zeros((n, k), dtype=self.dtype)
        if self.backend == "jax":
            block = min(block, self._op_row_chunk(self.W.shape[0]))
            with _x64_scope(self._use_x64):
                gl_w_d, w_d = _stage(self.gl, self.w)
        elif self.backend == "pallas":
            import jax

            from ..kernels import interpret_mode
            ref = self._block_factors()
            bd = self.block_dtype
        for i0 in range(0, n, block):
            i1 = min(i0 + block, n)
            if self.backend == "jax":
                from .jax_ops import swlc_topk
                with _x64_scope(self._use_x64):
                    gl_q_d, q_d = _stage(qs.gl[i0:i1], qs.q[i0:i1])
                    with span("engine.dispatch"):
                        v, ix = swlc_topk(gl_q_d, q_d, gl_w_d, w_d, kk)
                    del gl_q_d, q_d
                    v, ix = _fetch(v, ix)
            elif self.backend == "pallas":
                from .jax_ops import swlc_block_topk
                gl_q = qs.gl[i0:i1].astype(np.int32)
                q = qs.q[i0:i1].astype(bd)
                with jax.enable_x64(bd == np.float64):
                    staged = _stage(gl_q, q)
                    with span("engine.dispatch"):
                        v, ix = swlc_block_topk(
                            *staged, *ref, k=kk, n_ref=self.W.shape[0],
                            interpret=interpret_mode())
                    del staged
                    v, ix = _fetch(v, ix)
            else:
                B = self.kernel_block(np.arange(i0, i1), X_rows=X)
                part = np.argpartition(B, -kk, axis=1)[:, -kk:]
                pv = np.take_along_axis(B, part, axis=1)
                order = np.argsort(-pv, axis=1)
                ix = np.take_along_axis(part, order, axis=1)
                v = np.take_along_axis(pv, order, axis=1)
            idx[i0:i1, :kk] = ix
            val[i0:i1, :kk] = v
        return idx, val

    def _block_factors(self) -> tuple:
        """Reference side of the pallas block kernels: gl as int32 and w in
        ``block_dtype``, padded and transposed as
        ``block_prox.pad_reference`` lays them out, staged once per engine
        and kept on the device, as ``_ref_table`` keeps S: a warmed block
        op uploads only its query side.  The staging is the
        ``engine.ref_factors`` span (``bytes``: what is uploaded; no inner
        ``engine.upload``, so a reader summing ``bytes`` counts it once).
        Each lookup counts as ``hit`` or ``miss`` in
        ``engine_ref_factors_total{backend,result}``."""
        from ..obs.metrics import global_registry
        hit = self._block_ref is not None
        global_registry().counter(
            "engine_ref_factors_total",
            "reference factor lookups of the block kernels by result",
            labels=("backend", "result")).labels(
            backend=self.backend, result="hit" if hit else "miss").inc()
        if hit:
            return self._block_ref
        import jax
        import jax.numpy as jnp

        from ..kernels.block_prox.block_prox import (BLOCK_W, T_CHUNK,
                                                     pad_reference)
        gl = self.gl.astype(np.int32)
        w = self.w.astype(self.block_dtype)
        with span("engine.ref_factors", bytes=int(gl.nbytes + w.nbytes)), \
                jax.enable_x64(self.block_dtype == np.float64):
            staged = jnp.asarray(gl), jnp.asarray(w)
            with span("engine.dispatch"):
                self._block_ref = pad_reference(
                    *staged, block_w=BLOCK_W, t_chunk=T_CHUNK,
                    dtype=self.block_dtype)
            del staged
        return self._block_ref

    # ---------------- accounting ----------------
    def memory_bytes(self) -> dict:
        """Resident factor bytes per component; when a
        ``memory_budget_bytes`` is configured the report additionally
        carries the budget and whether the factors fit it, and both are
        pushed to the global metrics registry (``engine_memory_bytes``
        gauge family + ``engine_memory_budget_bytes``)."""
        from .leafmap import sparse_bytes
        dense = self.gl.nbytes + self.q.nbytes + \
            (0 if self.w is self.q else self.w.nbytes)
        out = {"dense_factors": int(dense), "Q": sparse_bytes(self.Q),
               "W": 0 if self.W is self.Q else sparse_bytes(self.W)}
        if self.leaf_values is not None:
            out["leaf_values"] = int(self.leaf_values.nbytes)
        out["total"] = sum(out.values())
        if self.memory_budget_bytes is not None:
            out["budget"] = int(self.memory_budget_bytes)
            out["within_budget"] = bool(out["total"] <= out["budget"])
        from ..obs.metrics import global_registry
        g = global_registry().gauge("engine_memory_bytes",
                                    "resident engine factor bytes",
                                    labels=("component",))
        for comp in ("dense_factors", "Q", "W", "total"):
            g.labels(component=comp).set(float(out[comp]))
        if self.memory_budget_bytes is not None:
            global_registry().gauge(
                "engine_memory_budget_bytes",
                "configured engine memory budget").set(float(out["budget"]))
        return out


def prediction_margin(scores: np.ndarray) -> np.ndarray:
    """Per-row confidence of proximity-vote class scores.

    margin_i = (top1_i - top2_i) / Σ_c scores[i, c] — the normalized vote
    gap, in [0, 1].  The tiered server escalates a request to a heavier
    engine when ``min_i margin_i`` falls below its threshold.  Rows with a
    single class column (or none) are fully confident by convention.
    """
    s = np.asarray(scores, dtype=np.float64)
    if s.ndim != 2 or s.shape[1] < 2:
        return np.full(s.shape[0] if s.ndim else 1, np.inf)
    top2 = -np.partition(-s, 1, axis=1)[:, :2]
    tot = np.maximum(s.sum(axis=1), np.finfo(np.float64).tiny)
    return (top2[:, 0] - top2[:, 1]) / tot


class PrefixProximityEngine(ProximityEngine):
    """Depth-k prefix tier: the proximity engine of the depth-truncated
    forest (DiNo/RanBu), derived from an already-fitted parent engine.

    Truncating every tree at depth k induces a *leaf contraction*: each full
    leaf has a unique ancestor at depth <= k, so the prefix forest's leaf
    codes are a pure gather ``gl_k = gmap[gl_full]`` of the parent's routed
    codes.  Training factors are contracted once at construction;
    out-of-sample batches reuse the parent's routed/cached query state, so
    one forest pass per batch serves every tier of the ladder.
    """

    def __init__(self, parent: ProximityEngine, depth: int,
                 oos_cache_size: int = 8, ref_cache_size: int = 16):
        from .context import EnsembleContext
        from .weights import get_assignment
        if parent.forest is None:
            raise ValueError("prefix tiers need the backing forest")
        self.parent = parent
        self.depth = int(depth)
        gmap, _, leaf_offset_k = prefix_leaf_contraction(
            parent.forest.trees_, self.depth)
        self._gmap = gmap
        self._leaf_offset_k = leaf_offset_k
        trunc = parent.forest.truncated(self.depth)
        pctx = parent.ctx
        leaves_k = (gmap[pctx.global_leaves()] -
                    leaf_offset_k[None, :]).astype(np.int32)
        ctx_k = EnsembleContext.from_forest(trunc, X=pctx.X, y=pctx.y,
                                            leaves=leaves_k)
        super().__init__(ctx_k, get_assignment(parent.assignment.name, ctx_k),
                         forest=trunc, backend=parent.backend,
                         dtype=parent.dtype, oos_cache_size=oos_cache_size,
                         ref_cache_size=ref_cache_size)

    def query_state(self, X: Optional[np.ndarray] = None) -> QueryState:
        """Contract the parent's routed state instead of re-routing."""
        if X is None:
            return self._train_state
        with span("engine.batch_key"):
            key = self._batch_key(np.asarray(X))
        hit = self._qs_cache_get(key)
        if hit is not None:
            return hit
        full = self.parent.query_state(X)      # routed once, shared by tiers
        gl = self._gmap[full.gl]
        leaves_k = gl - self._leaf_offset_k[None, :]
        with span("engine.weights"):
            q = np.ascontiguousarray(
                self.assignment.oos_query_weights(leaves_k), dtype=self.dtype)
        with span("engine.leaf_map"):
            Q = build_leaf_map(gl, q, self.total_leaves, self.dtype)
        return self._qs_cache_put(key, QueryState(gl=gl, q=q, Q=Q))
