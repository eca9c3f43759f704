"""Level-wise histogram CART training (numpy + native C backends).

This is the CPU trainer used for the paper-scale experiments (hundreds of
thousands of samples).  It follows the LightGBM/sklearn-HistGradientBoosting
design: features are pre-binned to ``n_bins`` quantile bins, and at each tree
level the class/moment histograms of *all* active nodes are accumulated in one
vectorized pass over a flattened (node, feature, bin[, class]) index.  Total
histogram work per level is ``O(N_inbag * d)`` independent of the node count,
so growing to purity costs ``O(N d depth)`` per tree — the ``O(N T h̄)``
training term of the paper's §3.3.

The three per-level hot loops — histogram accumulation, best-split scoring,
and sample partition — run through one of three backends selected by
``TreeParams.tree_backend``:

  ``numpy``   tiled ``np.bincount`` histograms (int32 flat indices when they
              fit, feature-tiled so no ``(m, d)`` weight blow-up is ever
              materialized) + vectorized cumsum scoring,
  ``native``  C kernels (``train_hist`` / ``train_best_split`` /
              ``train_partition`` in ``forest/_native.py``; OpenMP, float64
              accumulators, uint8 bin codes),
  ``jax``     the one-hot-MXU histogram/moments kernels in
              ``repro/kernels/histogram`` (pallas on accelerators, jitted
              scatter-add oracle elsewhere) with best-split scoring jitted
              on-device in the same operation order as ``_best_splits``;
              partition stays on the host so trees flow back through the
              same ``_TreeStore`` machinery.  Conformance is
              agreement-bounded (float32 histogram accumulation): trees are
              identical to the CPU backends on exact-representable
              integer-weight data, and downstream-kernel-close otherwise,
  ``auto``    native when a host compiler is available and codes fit uint8.

All backends share the **histogram-subtraction trick**: when a level's
parent histograms were retained (small frontiers, ``_SUB_MAX_PARENTS``
gate), only the smaller child of each sibling pair is accumulated and the
other is derived as ``parent − child`` — float64 (exact for the integer
bootstrap weights forests actually use) on numpy/native, float32 on jax —
halving histogram work on the shallow, full-``N`` levels that dominate.

The CPU backends grow **bit-identical trees**: every RNG draw happens here in
Python (per tree, chunk-aligned), the C kernels accumulate each histogram
bin in the same sample order numpy's ``bincount`` does (each (node,
feature-stripe) is owned by one thread), and split scores are evaluated with
the same float64 operation order on both paths, with first-maximum
tie-breaking on equal gains.  Because of that, a whole forest can be grown
as *one* level-synchronous batch (`fit_forest_binned`): each level makes a
single native call spanning every tree's frontier, so OpenMP threads stay
saturated even at deep, narrow levels — this replaces thread-pool-per-tree
parallelism on the native path (and composes with OMP_NUM_THREADS without
``n_jobs × OMP`` oversubscription).

The TPU-native counterpart (one-hot × matmul histograms) lives in
``repro/kernels/histogram``; this module is the reference/production CPU path.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..obs.metrics import global_registry
from .trees import Tree

__all__ = ["TreeParams", "Binner", "fit_tree", "fit_tree_binned",
           "fit_forest_binned", "resolve_tree_backend"]

_HIST_BUDGET = 1 << 26  # max float64 elements per histogram chunk (~512MB)
_TILE_ELEMS = 1 << 20   # max elements per transient index tile (numpy hist)
_EARLY_PRUNE = True     # drop known-leaf children's samples from the frontier
_BATCH_BUDGET = 1 << 28  # resident frontier bytes per multi-tree batch
_HIST_SUBTRACT = True   # derive sibling histograms as parent - smaller child
_SUB_MAX_PARENTS = 16   # retain parent hists only while a tree's level is
#                         this narrow (bounds stash memory; shallow levels
#                         scan the full sample set, so that's where the
#                         halved histogram work pays anyway)
_JAX_TILE = 512         # sample tile per pallas grid step (jax backend)
_JAX_NODE_CHUNK = 64    # node sub-chunk handed to kernels/histogram/ops
_JAX_USE_PALLAS = None  # None: compiled pallas off the CPU, XLA reference on it
_JAX_INTERPRET = None   # forwarded to ops.resolve_interpret (None = CPU only)


@dataclasses.dataclass
class TreeParams:
    task: str = "classification"      # "classification" | "regression"
    n_classes: int = 2
    max_depth: int = 64
    min_samples_leaf: int = 1
    min_samples_split: int = 2
    max_features: Optional[str] = "sqrt"   # "sqrt" | "log2" | None (all) | int
    n_bins: int = 64
    splitter: str = "best"            # "best" (CART) | "random" (ExtraTrees)
    tree_backend: str = "auto"        # "auto" | "numpy" | "native" | "jax"
    float32_hist: bool = False        # numpy/native: score splits from
    #                                   float32-cast histograms (the jax
    #                                   backend's accumulation precision)

    def n_feature_subset(self, d: int) -> int:
        mf = self.max_features
        if mf is None:
            return d
        if mf == "sqrt":
            return max(1, int(np.sqrt(d)))
        if mf == "log2":
            return max(1, int(np.log2(d)))
        return max(1, min(int(mf), d))


def resolve_tree_backend(backend: Optional[str], n_bins: int) -> str:
    """Resolve 'auto'|'numpy'|'native'|'jax' to a concrete trainer backend.

    The native kernels store bin codes as uint8, so they require
    ``n_bins <= 256``; 'auto' silently falls back to numpy outside that
    envelope (or when no host C compiler exists), 'native' raises.  'jax'
    requires jax to be importable ('auto' never selects it — accelerator
    training is opt-in).
    """
    if backend in (None, "auto"):
        from . import _native
        return "native" if (_native.available() and n_bins <= 256) else "numpy"
    if backend == "native":
        from . import _native
        if not _native.available():
            raise RuntimeError("native tree backend unavailable "
                               "(no working C compiler)")
        if n_bins > 256:
            raise ValueError("native tree backend requires n_bins <= 256 "
                             "(uint8 bin codes)")
        return "native"
    if backend == "jax":
        try:
            from ..kernels.histogram import ops as _ops  # noqa: F401
        except Exception as exc:  # pragma: no cover - env without jax
            raise RuntimeError(f"jax tree backend unavailable: {exc}")
        return "jax"
    if backend == "numpy":
        return "numpy"
    raise ValueError(f"unknown tree backend {backend!r}; have "
                     "'auto' | 'numpy' | 'native' | 'jax'")


class Binner:
    """Quantile pre-binning of a feature matrix to small integer codes.

    Vectorized over features: all quantile edges come from a single
    ``np.quantile(sub, qs, axis=0)`` call, stored offset-concatenated
    (``edges_flat`` / ``edge_offset`` / ``edge_count``), and ``transform``
    bins every feature in one broadcast pass per sample chunk.  Codes are
    ``uint8`` whenever ``n_bins <= 256`` (halving trainer bandwidth),
    ``int16`` otherwise.
    """

    def __init__(self, X: np.ndarray, n_bins: int = 64,
                 rng: Optional[np.random.Generator] = None):
        n, d = X.shape
        rng = rng or np.random.default_rng(0)
        sub = X if n <= 200_000 else X[rng.choice(n, 200_000, replace=False)]
        qs = np.linspace(0, 1, n_bins + 1)[1:-1]
        Q = np.quantile(sub, qs, axis=0)           # (n_q, d), monotone per col
        # Dedupe per column and drop the global max as an edge (it would
        # create an empty bin) — the vectorized form of per-feature
        # ``np.unique(...)[ ... < max]``.
        keep = np.ones(Q.shape, dtype=bool)
        if len(Q) > 1:
            keep[1:] = Q[1:] != Q[:-1]
        keep &= Q < sub.max(axis=0)[None, :]
        cnt = keep.sum(axis=0).astype(np.int64)
        self.edge_count = cnt
        self.edge_offset = np.concatenate(
            [[0], np.cumsum(cnt)]).astype(np.int64)
        self.edges_flat = np.ascontiguousarray(Q.T[keep.T], dtype=np.float64)
        self.n_bins = int(max(2, cnt.max(initial=0) + 1))
        self._build_pad_edges()

    def _build_pad_edges(self) -> None:
        """Padded (d, E) edge matrix for the one-pass transform; NaN pads
        never count in >= comparisons."""
        d, cnt = len(self.edge_count), self.edge_count
        E = max(int(cnt.max(initial=0)), 1)
        pad = np.full((d, E), np.nan)
        if len(self.edges_flat):
            rr = np.repeat(np.arange(d), cnt)
            cc = np.arange(len(self.edges_flat)) - np.repeat(
                self.edge_offset[:-1], cnt)
            pad[rr, cc] = self.edges_flat
        self._pad_edges = pad

    @classmethod
    def from_state(cls, edges_flat: np.ndarray, edge_offset: np.ndarray,
                   edge_count: np.ndarray, n_bins: int) -> "Binner":
        """Rebuild a fitted Binner from its saved edge arrays (snapshot
        load path) — ``transform`` is bit-identical to the original."""
        self = cls.__new__(cls)
        self.edge_count = np.asarray(edge_count, dtype=np.int64)
        self.edge_offset = np.asarray(edge_offset, dtype=np.int64)
        self.edges_flat = np.ascontiguousarray(edges_flat, dtype=np.float64)
        self.n_bins = int(n_bins)
        self._build_pad_edges()
        return self

    @property
    def edges(self) -> List[np.ndarray]:
        """Per-feature edge arrays (views into ``edges_flat``)."""
        return [self.edges_flat[self.edge_offset[f]:self.edge_offset[f + 1]]
                for f in range(len(self.edge_count))]

    @property
    def code_dtype(self) -> np.dtype:
        """Dtype of the emitted bin codes (uint8 iff they fit a byte)."""
        return np.dtype(np.uint8 if self.n_bins <= 256 else np.int16)

    def transform(self, X: np.ndarray, out: Optional[np.ndarray] = None
                  ) -> np.ndarray:
        """Map raw features to bin codes; bin(x) <= b  <=>  x <= edges[b].

        One broadcast comparison pass per sample chunk (no per-feature
        Python loop); exact ``searchsorted(edges_f, x, side='left')``
        semantics including NaN (which bins past the last edge).

        ``out`` streams the codes into a preallocated (n, d) array of
        :attr:`code_dtype` — typically an ``np.memmap`` — so only one
        (chunk, d, E) comparison transient is ever resident.  ``X`` itself
        may be disk-backed; it is read in the same row chunks.  The chunk
        sweep is identical with or without ``out``, so streamed codes are
        bit-identical to the in-RAM result.
        """
        n, d = X.shape
        dt = self.code_dtype
        if out is None:
            out = np.empty((n, d), dtype=dt)
        elif out.shape != (n, d) or out.dtype != dt:
            raise ValueError(
                f"out must be shape {(n, d)} dtype {dt}, got "
                f"{out.shape} {out.dtype}")
        pe = self._pad_edges
        cnt = self.edge_count[None, :]
        chunk = max(1, int(_TILE_ELEMS * 4) // max(pe.shape[1] * d, 1))
        for i0 in range(0, n, chunk):
            x = np.asarray(X[i0:i0 + chunk])
            ge = pe[None, :, :] >= x[:, :, None]     # (c, d, E)
            out[i0:i0 + chunk] = (cnt - ge.sum(axis=2)).astype(dt)
        return out

    def transform_memmap(self, X: np.ndarray, path) -> np.memmap:
        """Stream-bin ``X`` into a disk-backed code matrix at ``path``.

        Creates an ``np.memmap`` (mode ``w+``) of shape (n, d) with the
        binner's :attr:`code_dtype`, fills it chunk-by-chunk through
        :meth:`transform`, flushes, and returns the live mapping.  The
        numpy/native trainers accept the result directly and grow trees
        bit-identical to the in-RAM codes (histogram/partition passes read
        disk-backed codes in bounded row chunks).
        """
        n, d = X.shape
        mm = np.memmap(path, dtype=self.code_dtype, mode="w+", shape=(n, d))
        self.transform(X, out=mm)
        mm.flush()
        return mm

    def threshold(self, f: int, b: int) -> float:
        c = int(self.edge_count[f])
        if not c:
            return np.inf
        return float(self.edges_flat[self.edge_offset[f] + min(b, c - 1)])

    def thresholds(self, f: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Vectorized ``threshold`` over (feature, bin) arrays."""
        f = np.asarray(f, dtype=np.int64)
        b = np.asarray(b, dtype=np.int64)
        if not len(self.edges_flat):
            return np.full(f.shape, np.inf)
        c = self.edge_count[f]
        idx = self.edge_offset[f] + np.minimum(b, np.maximum(c - 1, 0))
        out = self.edges_flat[np.minimum(idx, len(self.edges_flat) - 1)]
        return np.where(c > 0, out, np.inf)


def _as_code_matrix(Xb: np.ndarray) -> np.ndarray:
    """Normalize a binned-code matrix without destroying memmap-ness.

    ``np.asarray`` on an ``np.memmap`` returns a plain-ndarray *view* and
    the trainer could no longer tell the codes are disk-resident; keeping
    the subclass lets the histogram passes switch to bounded row-chunked
    reads (`_is_streamed`).
    """
    return Xb if isinstance(Xb, np.ndarray) else np.asarray(Xb)


def _is_streamed(Xb: np.ndarray) -> bool:
    """True when the code matrix is disk-backed and must be read in bounded
    row chunks instead of one (m, d) frontier gather."""
    return isinstance(Xb, np.memmap)


def _node_values(y: np.ndarray, w: np.ndarray, params: TreeParams) -> np.ndarray:
    if params.task == "classification":
        return np.bincount(y, weights=w, minlength=params.n_classes).astype(np.float32)
    tot = w.sum()
    return np.array([tot, (w * y).sum() / max(tot, 1e-12)], dtype=np.float32)


def fit_tree(X: np.ndarray, y: np.ndarray, w: np.ndarray, params: TreeParams,
             rng: np.random.Generator, binner: Optional[Binner] = None) -> Tree:
    binner = binner or Binner(X, params.n_bins, rng)
    Xb = binner.transform(X)
    return fit_tree_binned(Xb, y, w, params, rng, binner)


def fit_tree_binned(Xb: np.ndarray, y: np.ndarray, w: np.ndarray,
                    params: TreeParams, rng: np.random.Generator,
                    binner: Binner) -> Tree:
    """Grow one tree level-wise on pre-binned features.

    ``w`` are per-sample weights (bootstrap multiplicities); samples with
    ``w == 0`` must be excluded by the caller (they are OOB).
    """
    backend = resolve_tree_backend(params.tree_backend, binner.n_bins)
    rows = np.arange(Xb.shape[0], dtype=np.int64)
    task = (rows, np.asarray(w, dtype=np.float64), rng)
    return _grow_trees(_as_code_matrix(Xb), np.asarray(y), [task], params,
                       binner, backend)[0]


def fit_forest_binned(Xb: np.ndarray, y: np.ndarray, inbag: np.ndarray,
                      params: TreeParams, rngs: Sequence[np.random.Generator],
                      binner: Binner, backend: Optional[str] = None,
                      tree_block: int = 0) -> List[Tree]:
    """Grow a whole forest as level-synchronous batches of trees.

    Each level issues ONE histogram/score/partition pass spanning every
    tree's frontier, so the native kernels see a wide node set even when
    individual trees are deep and narrow.  ``tree_block`` caps how many
    trees share a batch: 0 (default) auto-sizes the cap so resident
    frontier state (instance rows/weights/labels + the partition double
    buffer, ~48 bytes per in-bag instance) stays under ``_BATCH_BUDGET``;
    negative means all trees in one batch.  Trees are bit-identical to
    growing each alone with its own spawned RNG stream (any backend, any
    block size).
    """
    backend = resolve_tree_backend(
        backend if backend is not None else params.tree_backend, binner.n_bins)
    T = inbag.shape[0]
    if tree_block == 0:
        m_avg = max(1.0, float((inbag > 0).sum()) / max(T, 1))
        block = int(max(1, min(T, _BATCH_BUDGET // int(48 * m_avg))))
    elif tree_block < 0:
        block = T
    else:
        block = max(1, int(tree_block))
    Xb = _as_code_matrix(Xb)
    trees: List[Tree] = []
    for b0 in range(0, T, block):
        tasks = []
        for t in range(b0, min(b0 + block, T)):
            rows = np.nonzero(inbag[t])[0].astype(np.int64)
            tasks.append((rows, inbag[t, rows].astype(np.float64), rngs[t]))
        trees += _grow_trees(Xb, y, tasks, params, binner, backend)
    return trees


# --------------------------------------------------------------------------
# shared level-wise driver
# --------------------------------------------------------------------------

class _TreeStore:
    """Growable struct-of-arrays node store for one tree."""

    __slots__ = ("feat", "thr", "left", "right", "val", "cnt", "n",
                 "last_level")

    def __init__(self, value_dim: int):
        cap = 64
        self.feat = np.full(cap, -2, np.int64)   # -2 unresolved, -1 leaf
        self.thr = np.full(cap, np.inf, np.float64)
        self.left = np.zeros(cap, np.int64)
        self.right = np.zeros(cap, np.int64)
        self.val = np.zeros((cap, value_dim), np.float32)
        self.cnt = np.zeros(cap, np.float64)
        self.n = 0
        self.last_level = 0

    def alloc(self, m: int) -> int:
        need = self.n + m
        cap = len(self.feat)
        if need > cap:
            new = max(need, 2 * cap)

            def grow(a, fill):
                b = np.empty((new,) + a.shape[1:], a.dtype)
                b[:cap] = a
                b[cap:] = fill
                return b

            self.feat = grow(self.feat, -2)
            self.thr = grow(self.thr, np.inf)
            self.left = grow(self.left, 0)
            self.right = grow(self.right, 0)
            self.val = grow(self.val, 0)
            self.cnt = grow(self.cnt, 0.0)
        base = self.n
        self.n = need
        return base

    def to_tree(self) -> Tree:
        n = self.n
        return Tree.from_growth(
            self.feat[:n], self.thr[:n], self.left[:n], self.right[:n],
            self.val[:n], self.cnt[:n],
            depth=self.last_level + 1 if self.last_level else 1)


class _LevelDraws:
    """Per-level RNG draws for one tree, generated chunk-by-chunk in the
    tree's own chunk order — the conformance-critical stream order: per
    chunk, splitter-u first, then the feature-subset mask — but *served*
    lazily for ascending node-range slices.  Only the window between the
    last consumed node and the highest requested one is ever resident, so
    splitter-u memory stays bounded by the hist-chunk width instead of the
    whole level."""

    __slots__ = ("rng", "n_act", "d", "B", "chunk", "random_split", "k",
                 "_gen", "_off", "_parts_u", "_parts_m")

    def __init__(self, rng: np.random.Generator, n_act: int, d: int, B: int,
                 chunk_nodes: int, random_split: bool, k: int):
        self.rng, self.n_act, self.d, self.B = rng, n_act, d, B
        self.chunk, self.random_split, self.k = chunk_nodes, random_split, k
        self._gen = 0        # nodes drawn so far
        self._off = 0        # node index of the first retained part row
        self._parts_u: List[np.ndarray] = []
        self._parts_m: List[np.ndarray] = []

    def take(self, lo: int, hi: int
             ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """Draw slices covering node range [lo, hi); ranges must be
        requested in ascending order (fully-consumed parts are freed)."""
        while self._gen < hi:
            c = min(self.chunk, self.n_act - self._gen)
            if self.random_split:
                self._parts_u.append(self.rng.random((c, self.d, self.B)))
            if self.k < self.d:
                cols = self.rng.random((c, self.d)).argsort(axis=1)[:, :self.k]
                mk = np.zeros((c, self.d), dtype=bool)
                np.put_along_axis(mk, cols, True, axis=1)
                self._parts_m.append(mk)
            self._gen += c
        u_out: List[np.ndarray] = []
        m_out: List[np.ndarray] = []
        for parts, out in ((self._parts_u, u_out), (self._parts_m, m_out)):
            pos = self._off
            for p in parts:
                if pos + len(p) > lo and pos < hi:
                    out.append(p[max(lo - pos, 0):hi - pos])
                pos += len(p)
        src = self._parts_u if self._parts_u else self._parts_m
        ndrop = 0
        for p in src:
            if self._off + len(p) > hi:
                break
            self._off += len(p)
            ndrop += 1
        del self._parts_u[:ndrop]
        del self._parts_m[:ndrop]
        return u_out, m_out


def _hist_numpy(Xb: np.ndarray, rows: np.ndarray, w: np.ndarray,
                y_inst: np.ndarray, bounds: np.ndarray, d: int, B: int,
                C: int, cls: bool) -> np.ndarray:
    """(gc, d, B, C) float64 histograms via tiled flat bincounts.

    Feature-tiled so the transient index/weight arrays stay under
    ``_TILE_ELEMS`` elements (no ``np.repeat(w, d)`` blow-up), with int32
    flat indices whenever ``gc * d * B * C < 2**31``.  Per-bin accumulation
    order is sample order — identical to the untiled bincount and to the
    native kernel.

    A disk-backed (memmap) ``Xb`` skips the upfront (m, d) frontier gather
    and instead gathers each feature tile's (m, td) codes directly — the
    tile is already bounded to ``_TILE_ELEMS`` elements, and exactly ONE
    bincount per tile is kept either way, so the per-bin float accumulation
    order (and hence the grown trees) is bit-identical to the in-RAM path.
    """
    gc = len(bounds) - 1
    hist = np.zeros((gc, d, B, C), dtype=np.float64)
    m = len(rows)
    if m == 0 or gc == 0:
        return hist
    size = gc * d * B
    idx_dt = np.int32 if size * C < 2 ** 31 else np.int64
    loc = np.repeat(np.arange(gc, dtype=idx_dt), np.diff(bounds))
    stream = _is_streamed(Xb)
    codes = None if stream else Xb[rows]              # (m, d) small dtype
    td_max = max(1, min(d, int(_TILE_ELEMS // max(m, 1))))
    if cls:
        yl = y_inst.astype(idx_dt)
    else:
        wy = w * y_inst
        wy2 = w * (y_inst * y_inst)
    for f0 in range(0, d, td_max):
        f1 = min(f0 + td_max, d)
        td = f1 - f0
        ct = np.asarray(Xb[rows, f0:f1]) if stream else codes[:, f0:f1]
        base = (loc[:, None] * np.int64(td).astype(idx_dt)
                + np.arange(td, dtype=idx_dt)[None, :]) * B \
            + ct.astype(idx_dt)
        tsize = gc * td * B
        if cls:
            flat = base * C + yl[:, None]
            hist[:, f0:f1] = np.bincount(
                flat.ravel(), weights=np.repeat(w, td),
                minlength=tsize * C).reshape(gc, td, B, C)
        else:
            fr = base.ravel()
            hist[:, f0:f1] = np.stack([
                np.bincount(fr, weights=np.repeat(w, td),
                            minlength=tsize).reshape(gc, td, B),
                np.bincount(fr, weights=np.repeat(wy, td),
                            minlength=tsize).reshape(gc, td, B),
                np.bincount(fr, weights=np.repeat(wy2, td),
                            minlength=tsize).reshape(gc, td, B),
            ], axis=-1)
    return hist


def _seq_sum_last(a: np.ndarray) -> np.ndarray:
    """Sum over the last axis in strictly sequential channel order (the
    exact operation order of the native kernel)."""
    s = a[..., 0].copy()
    for c in range(1, a.shape[-1]):
        s += a[..., c]
    return s


def _seq_sq_last(a: np.ndarray) -> np.ndarray:
    s = a[..., 0] * a[..., 0]
    for c in range(1, a.shape[-1]):
        s += a[..., c] * a[..., c]
    return s


def _best_splits(hist: np.ndarray, msl: float, cls: bool, random_split: bool,
                 u: Optional[np.ndarray], mask: Optional[np.ndarray]):
    """Pick the best (feature, bin) split per node from histograms.

    hist: (nodes, d, bins, C).  Returns (gain, feature, bin, node_totals).
    Float64 throughout; ties broken to the first (lowest-index) maximum —
    both properties shared with the native ``train_best_split`` kernel.
    """
    gain, node_tot = split_gains(hist, msl, cls)
    if random_split:
        # ExtraTrees: one random valid bin per (node, feature).
        uu = np.where(gain > -np.inf, u, -np.inf)
        rb = uu.argmax(axis=2)
        gain = np.take_along_axis(gain, rb[:, :, None], axis=2)[:, :, 0]
        bins_choice = rb
    else:
        bins_choice = gain.argmax(axis=2)
        gain = np.take_along_axis(gain, bins_choice[:, :, None], axis=2)[:, :, 0]

    if mask is not None:                          # per-node feature subset
        gain = np.where(mask, gain, -np.inf)

    f_best = gain.argmax(axis=1)
    g_best = np.take_along_axis(gain, f_best[:, None], axis=1)[:, 0]
    b_best = np.take_along_axis(bins_choice, f_best[:, None], axis=1)[:, 0]
    return g_best, f_best, b_best, node_tot


def split_gains(hist: np.ndarray, msl: float, cls: bool):
    """(gain, node_totals): the float64 gain of every (node, feature, bin)
    split of ``hist`` (nodes, d, bins, C), -inf where a side would hold
    fewer than ``msl``; the scoring half of ``_best_splits``."""
    cum = np.cumsum(hist, axis=2)                      # left stats at bin b
    tot = cum[:, :, -1:, :]                            # (nodes, d, 1, C)
    R = tot - cum
    if cls:
        nL = _seq_sum_last(cum)
        nR = _seq_sum_last(R)
        score = _seq_sq_last(cum) / np.maximum(nL, 1e-12)
        score += _seq_sq_last(R) / np.maximum(nR, 1e-12)
        p0 = tot[:, 0, 0, :]
        parent = _seq_sq_last(p0) / np.maximum(_seq_sum_last(p0), 1e-12)
        gain = score - parent[:, None, None]
        node_tot = np.ascontiguousarray(p0)
    else:
        nL, nR = cum[..., 0], R[..., 0]
        score = cum[..., 1] ** 2 / np.maximum(nL, 1e-12)
        score += R[..., 1] ** 2 / np.maximum(nR, 1e-12)
        parent = tot[..., 0, 1] ** 2 / np.maximum(tot[..., 0, 0], 1e-12)
        gain = score - parent[:, :, None]
        node_tot = np.ascontiguousarray(tot[:, 0, 0, :])

    valid = (nL >= msl) & (nR >= msl)
    valid[:, :, -1] = False                       # last bin -> empty right side
    return np.where(valid, gain, -np.inf), node_tot


def _ranges_concat(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenate index ranges [starts[k], starts[k]+lens[k]) into one array."""
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.int64)
    off = np.concatenate([[0], np.cumsum(lens)[:-1]])
    return np.repeat(starts - off, lens) + np.arange(total)


@functools.lru_cache(maxsize=None)
def _jax_scorer(cls: bool, random_split: bool, has_mask: bool, msl: float,
                dt_name: str):
    """Jitted on-device mirror of ``_best_splits``.

    Same operation order (cumsum over bins, sequential channel reduction,
    two-term score add, first-maximum argmax tie-breaks); ``dt_name`` is the
    scoring dtype — float64 when x64 is enabled, which on exact-integer
    histograms makes gains bit-equal to the numpy path.
    """
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(dt_name)

    def _sum_last(a):
        s = a[..., 0]
        for c in range(1, a.shape[-1]):
            s = s + a[..., c]
        return s

    def _sq_last(a):
        s = a[..., 0] * a[..., 0]
        for c in range(1, a.shape[-1]):
            s = s + a[..., c] * a[..., c]
        return s

    def score(hist, u, mask):
        cum = jnp.cumsum(hist.astype(dt), axis=2)
        tot = cum[:, :, -1:, :]
        R = tot - cum
        if cls:
            nL, nR = _sum_last(cum), _sum_last(R)
            sc = _sq_last(cum) / jnp.maximum(nL, 1e-12) \
                + _sq_last(R) / jnp.maximum(nR, 1e-12)
            p0 = tot[:, 0, 0, :]
            parent = _sq_last(p0) / jnp.maximum(_sum_last(p0), 1e-12)
            gain = sc - parent[:, None, None]
            node_tot = p0
        else:
            nL, nR = cum[..., 0], R[..., 0]
            sc = cum[..., 1] ** 2 / jnp.maximum(nL, 1e-12) \
                + R[..., 1] ** 2 / jnp.maximum(nR, 1e-12)
            parent = tot[..., 0, 1] ** 2 / jnp.maximum(tot[..., 0, 0], 1e-12)
            gain = sc - parent[:, :, None]
            node_tot = tot[:, 0, 0, :]

        valid = (nL >= msl) & (nR >= msl)
        valid = valid.at[:, :, -1].set(False)
        gain = jnp.where(valid, gain, -jnp.inf)
        if random_split:
            uu = jnp.where(valid, u.astype(dt), -jnp.inf)
            bins_choice = uu.argmax(axis=2)
        else:
            bins_choice = gain.argmax(axis=2)
        gain = jnp.take_along_axis(
            gain, bins_choice[:, :, None], axis=2)[:, :, 0]
        if has_mask:
            gain = jnp.where(mask, gain, -jnp.inf)
        f_best = gain.argmax(axis=1)
        g_best = jnp.take_along_axis(gain, f_best[:, None], axis=1)[:, 0]
        b_best = jnp.take_along_axis(
            bins_choice, f_best[:, None], axis=1)[:, 0]
        return g_best, f_best, b_best, node_tot

    return jax.jit(score)


def _partition_numpy(Xb: np.ndarray, rows: np.ndarray, w: np.ndarray,
                     y_inst: np.ndarray, bounds: np.ndarray,
                     split: np.ndarray, best_f: np.ndarray,
                     best_b: np.ndarray, cls: bool, Cv: int):
    """Partition split nodes' samples into child order.

    Returns (rows_next, w_next, child_counts, csum): instances of split
    nodes reordered as [left block, right block] per node (stable within a
    side), per-child instance counts, and per-child payload sums
    (class-weight rows for classification, (Σw, Σwy) for regression).
    """
    gc = len(bounds) - 1
    counts = np.diff(bounds)
    loc = np.repeat(np.arange(gc, dtype=np.int64), counts)
    keep = split[loc]
    rowsk, wk, yk, lock = rows[keep], w[keep], y_inst[keep], loc[keep]
    go_left = Xb[rowsk, best_f[lock]] <= best_b[lock]
    srank = np.cumsum(split) - 1                      # split rank per node
    child_slot = 2 * srank[lock] + (~go_left).astype(np.int64)
    n_child = 2 * int(split.sum())
    order = np.argsort(child_slot, kind="stable")
    rows_next = rowsk[order]
    w_next = wk[order]
    child_counts = np.bincount(child_slot, minlength=n_child).astype(np.int64)
    if cls:
        csum = np.bincount(child_slot * Cv + yk, weights=wk,
                           minlength=n_child * Cv).reshape(n_child, Cv)
    else:
        cw = np.bincount(child_slot, weights=wk, minlength=n_child)
        cwy = np.bincount(child_slot, weights=wk * yk, minlength=n_child)
        csum = np.stack([cw, cwy], axis=1)
    return rows_next, w_next, child_counts, csum


def _grow_trees(Xb: np.ndarray, y: np.ndarray, tasks: Sequence[tuple],
                params: TreeParams, binner: Binner, backend: str) -> List[Tree]:
    """Grow a batch of trees level-synchronously (the shared driver).

    ``tasks`` is a sequence of ``(rows, w, rng)`` — global sample indices
    into ``Xb``, per-instance weights, and the tree's RNG stream.  All RNG
    consumption happens here (never in the kernels), per tree in the same
    chunked order regardless of backend or batch width, which is what makes
    numpy/native and batched/per-tree growth bit-identical.
    """
    n_all, d = Xb.shape
    B = int(binner.n_bins)
    cls = params.task == "classification"
    C = params.n_classes if cls else 3      # histogram channels
    Cv = params.n_classes if cls else 2     # stored value dim
    k = params.n_feature_subset(d)
    random_split = params.splitter == "random"
    msl = float(params.min_samples_leaf)
    chunk_nodes = max(1, int(_HIST_BUDGET // max(d * B * C, 1)))
    # Sibling pairs (children 2p, 2p+1) must never straddle a hist chunk for
    # the subtraction trick; per-tree node offsets are even from level 2 on,
    # so an even chunk width is sufficient.  RNG draws are chunk-invariant
    # (``Generator.random`` fills from a sequential stream), so this does
    # not perturb drawn values.
    if chunk_nodes > 1:
        chunk_nodes -= chunk_nodes % 2
    sub_on = _HIST_SUBTRACT and chunk_nodes % 2 == 0

    native = backend == "native"
    use_jax = backend == "jax"
    use_f32 = bool(params.float32_hist) and not use_jax
    nat = jnp = hops = None
    if native:
        from . import _native as nat
        Xb_k = np.ascontiguousarray(Xb, dtype=np.uint8)
        if d and len(Xb_k) and int(Xb_k.max()) >= B:
            raise ValueError(f"bin codes exceed binner.n_bins={B}")
    elif use_jax:
        import jax as _jax
        import jax.numpy as jnp
        from ..kernels.histogram import ops as hops
        Xb_k = Xb
        # disk-resident codes: skip the whole-matrix int32 device copy and
        # stage each histogram call's row gather to device instead (the
        # gather is bounded by the call's padded frontier chunk)
        Xb_dev = None if _is_streamed(Xb) else jnp.asarray(
            np.ascontiguousarray(Xb, dtype=np.int32))
        dt_name = str(_jax.dtypes.canonicalize_dtype(np.float64))
        from ..kernels import interpret_mode
        jax_pallas = (_JAX_USE_PALLAS if _JAX_USE_PALLAS is not None
                      else not interpret_mode())
    else:
        Xb_k = Xb
    yc = y.astype(np.int64) if cls else np.asarray(y, dtype=np.float64)

    if use_jax:
        def jax_hist(rows_c, loc_c, w_c, y_c, nn):
            """Device histograms via kernels/histogram/ops for one node
            range; samples are zero-weight padded to a power of two so the
            jitted kernels see log-many shapes per fit."""
            m = len(rows_c)
            if m == 0:
                return jnp.zeros((nn, d, B, C), jnp.float32)
            mp = max(_JAX_TILE, 1 << (m - 1).bit_length())
            idx = np.zeros(mp, np.int32)
            idx[:m] = rows_c
            nod = np.zeros(mp, np.int32)
            nod[:m] = loc_c
            if Xb_dev is None:       # memmap codes: host gather, then stage
                xb_dev = jnp.asarray(np.asarray(Xb[idx]).astype(np.int32))
            else:
                xb_dev = Xb_dev[jnp.asarray(idx)]
            if cls:
                yv = np.zeros(mp, np.int32)
                yv[:m] = y_c
                wv = np.zeros(mp, np.float32)
                wv[:m] = w_c
                return hops.histogram(
                    xb_dev, nod, yv, wv, nn, B, C, tile=_JAX_TILE,
                    use_pallas=jax_pallas, max_node_chunk=_JAX_NODE_CHUNK,
                    interpret=_JAX_INTERPRET)
            wm = np.zeros((mp, 3), np.float32)
            wm[:m, 0] = w_c
            wm[:m, 1] = w_c * y_c
            wm[:m, 2] = w_c * (y_c * y_c)
            return hops.moments(
                xb_dev, nod, wm, nn, B, tile=_JAX_TILE,
                use_pallas=jax_pallas, max_node_chunk=_JAX_NODE_CHUNK,
                interpret=_JAX_INTERPRET)

        def score_jax(hist_dev, gcc, u_ch, m_ch):
            """On-device best-split scoring; node count padded to a power of
            two (zero histograms score -inf and are sliced off)."""
            gp = 1 << max(0, int(gcc - 1).bit_length())
            if gp != gcc:
                hist_dev = jnp.concatenate(
                    [hist_dev,
                     jnp.zeros((gp - gcc,) + tuple(hist_dev.shape[1:]),
                               hist_dev.dtype)], axis=0)
            u_dev = m_dev = None
            if u_ch is not None:
                u_pad = np.zeros((gp, d, B), np.float64)
                u_pad[:gcc] = u_ch
                u_dev = jnp.asarray(u_pad)
            if m_ch is not None:
                m_pad = np.zeros((gp, d), bool)
                m_pad[:gcc] = m_ch
                m_dev = jnp.asarray(m_pad)
            fn = _jax_scorer(cls, random_split, m_ch is not None, msl,
                             dt_name)
            g_b, f_b, b_b, tot = fn(hist_dev, u_dev, m_dev)
            return (np.asarray(g_b, np.float64)[:gcc],
                    np.asarray(f_b).astype(np.int64)[:gcc],
                    np.asarray(b_b).astype(np.int64)[:gcc],
                    np.asarray(tot, np.float64)[:gcc])

    stores: List[_TreeStore] = []
    acts: List[np.ndarray] = []      # per-tree active node ids (store ids)
    rngs = []
    for rows, w, rng in tasks:
        st = _TreeStore(Cv)
        st.alloc(1)
        st.val[0] = _node_values(y[rows], w, params)
        st.cnt[0] = float(w.sum())
        stores.append(st)
        acts.append(np.zeros(1, np.int64))
        rngs.append(rng)

    # Histogram-subtraction state: per live tree, the retained split-node
    # histograms of the previous level (``ret_hist``, split-rank rows) and
    # the children's known-leaf flags (``ret_kl``) that gate which sibling
    # pairs may be derived instead of accumulated.
    ret_hist: dict = {}
    ret_kl: dict = {}

    # Level-global frontier state: instances of all live trees' active
    # nodes, sorted by (tree, node); the partition step emits the next
    # level's layout directly, so nothing is re-concatenated per level.
    live = list(range(len(tasks)))
    rows_g = np.ascontiguousarray(
        np.concatenate([t[0] for t in tasks]), dtype=np.int64)
    w_g = np.ascontiguousarray(
        np.concatenate([t[1] for t in tasks]), dtype=np.float64)
    bounds_g = np.concatenate(
        [[0], np.cumsum([len(t[0]) for t in tasks])]).astype(np.int64)
    # per-level profiling into the process-wide registry (no-op when the
    # global registry is disabled); one histogram observation + two gauge
    # sets per level is negligible against the histogram pass itself
    _reg = global_registry()
    _h_level = _reg.histogram(
        "train_level_seconds", "level-synchronous growth: one level",
        labels=("backend",)).labels(backend=backend)
    _c_levels = _reg.counter(
        "train_levels_total", "tree levels grown",
        labels=("backend",)).labels(backend=backend)
    _g_nodes = _reg.gauge("train_frontier_nodes",
                          "active nodes in the last-grown level")
    _g_rows = _reg.gauge("train_frontier_rows",
                         "frontier sample rows in the last-grown level")

    depth = 0
    while live and depth < params.max_depth:
        depth += 1
        _t_level = time.perf_counter()
        g_sizes = np.array([len(acts[t]) for t in live], np.int64)
        node_off = np.concatenate([[0], np.cumsum(g_sizes)]).astype(np.int64)
        G = int(node_off[-1])
        y_g = yc[rows_g]
        _g_nodes.set(G)
        _g_rows.set(len(rows_g))

        best_gain = np.empty(G)
        best_f = np.empty(G, np.int64)
        best_b = np.empty(G, np.int64)
        node_tot = np.empty((G, C))

        # Per-tree RNG draws, generated lazily per hist chunk (in each
        # tree's own chunk order) and freed as the chunk sweep passes them.
        draw_cache: dict = {}
        tree_for_node = np.repeat(np.arange(len(live)), g_sizes)

        # ---- histogram-subtraction plan for this level ----
        # ``dm`` marks nodes whose histogram is accumulated directly; a
        # derived node's histogram is ``ret_hist[parent] - hist[sibling]``.
        # A pair is derivable only when neither child is known-leaf-flagged
        # (flags are computed in both prune modes and flagged children
        # always become leaves, so prune on/off stays conformant); the
        # computed child is the smaller side (tie -> left).  All decisions
        # are per-tree or config-derived, so batched == per-tree holds.
        cnts_lvl = np.diff(bounds_g)
        dm = der_par = der_sib = None
        if sub_on and ret_hist:
            dm = np.ones(G, bool)
            der_par = np.zeros(G, np.int64)
            der_sib = np.zeros(G, np.int64)
            for i, t in enumerate(live):
                rh = ret_hist.get(t)
                if rh is None:
                    continue
                kl = ret_kl[t]
                o0i, g = int(node_off[i]), int(g_sizes[i])
                ns_prev = g // 2
                pair_ok = ~(kl[0::2] | kl[1::2])
                lc = cnts_lvl[o0i:o0i + g:2]
                rc = cnts_lvl[o0i + 1:o0i + g:2]
                left_small = lc <= rc
                base2 = 2 * np.arange(ns_prev, dtype=np.int64)
                der_loc = np.where(left_small, base2 + 1, base2)[pair_ok]
                sib_loc = np.where(left_small, base2, base2 + 1)[pair_ok]
                dm[o0i + der_loc] = False
                der_par[o0i + der_loc] = np.flatnonzero(pair_ok)
                der_sib[o0i + der_loc] = o0i + sib_loc
            if dm.all():
                dm = None
        stash_set = set()
        if sub_on:
            for i in range(len(live)):
                if g_sizes[i] <= _SUB_MAX_PARENTS:
                    stash_set.add(i)
        pend: dict = {}

        def draws_for(i: int) -> _LevelDraws:
            if i not in draw_cache:
                draw_cache[i] = _LevelDraws(
                    rngs[live[i]], int(g_sizes[i]), d, B, chunk_nodes,
                    random_split, k)
            return draw_cache[i]

        for c0 in range(0, G, chunk_nodes):
            c1 = min(c0 + chunk_nodes, G)
            s0, s1 = int(bounds_g[c0]), int(bounds_g[c1])
            bch = bounds_g[c0:c1 + 1] - s0
            u_ch = m_ch = None
            if random_split or k < d:
                u_parts, m_parts = [], []
                for i in range(int(tree_for_node[c0]),
                               int(tree_for_node[c1 - 1]) + 1):
                    lo = max(c0, int(node_off[i])) - int(node_off[i])
                    hi = min(c1, int(node_off[i + 1])) - int(node_off[i])
                    us, ms = draws_for(i).take(lo, hi)
                    u_parts += us
                    m_parts += ms
                if random_split:
                    u_ch = np.ascontiguousarray(
                        u_parts[0] if len(u_parts) == 1
                        else np.concatenate(u_parts))
                if k < d:
                    m_ch = np.ascontiguousarray(
                        m_parts[0] if len(m_parts) == 1
                        else np.concatenate(m_parts))
                for i in list(draw_cache):
                    if int(node_off[i + 1]) <= c1:
                        del draw_cache[i]

            gcc = c1 - c0
            i_lo, i_hi = int(tree_for_node[c0]), int(tree_for_node[c1 - 1])
            has_stash = any(i in stash_set for i in range(i_lo, i_hi + 1))
            dm_ch = dm[c0:c1] if dm is not None else None
            all_direct = dm_ch is None or bool(dm_ch.all())

            if native and all_direct and not has_stash and not use_f32:
                # fast path: fused native level kernel, no histogram ever
                # materialized (deep/wide levels land here)
                res = nat.train_level_native(
                    Xb_k, rows_g[s0:s1], w_g[s0:s1], y_g[s0:s1], bch, B, C,
                    cls, msl, u_ch, m_ch)
                (best_gain[c0:c1], best_f[c0:c1], best_b[c0:c1],
                 node_tot[c0:c1]) = res
                continue

            if not all_direct:
                dn = np.flatnonzero(dm_ch)
                dl = np.flatnonzero(~dm_ch)
                d_starts = bounds_g[dn + c0]
                d_lens = bounds_g[dn + c0 + 1] - d_starts
                sel = _ranges_concat(d_starts, d_lens)
                bnd_d = np.concatenate([[0], np.cumsum(d_lens)]) \
                    .astype(np.int64)

                def parent_rows():
                    """Stacked retained-parent hist rows aligned with ``dl``
                    (trees ascend with node index, so per-tree parts
                    concatenate in ``dl`` order)."""
                    parts = []
                    for i in range(i_lo, i_hi + 1):
                        rh = ret_hist.get(live[i])
                        if rh is None:
                            continue
                        o0i = int(node_off[i])
                        o1i = int(node_off[i + 1])
                        g_dl = dl[(dl + c0 >= o0i) & (dl + c0 < o1i)]
                        if len(g_dl):
                            parts.append(rh[der_par[g_dl + c0]])
                    return parts

            if use_jax:
                if all_direct:
                    loc = np.repeat(np.arange(gcc, dtype=np.int64),
                                    np.diff(bch))
                    hist = jax_hist(rows_g[s0:s1], loc, w_g[s0:s1],
                                    y_g[s0:s1], gcc)
                else:
                    loc = np.repeat(np.arange(len(dn), dtype=np.int64),
                                    d_lens)
                    h_dir = jax_hist(rows_g[sel], loc, w_g[sel], y_g[sel],
                                     len(dn))
                    hist = jnp.zeros((gcc, d, B, C), jnp.float32) \
                        .at[jnp.asarray(dn)].set(h_dir)
                    sib = np.searchsorted(dn, der_sib[dl + c0] - c0)
                    par = jnp.concatenate(parent_rows(), axis=0)
                    hist = hist.at[jnp.asarray(dl)].set(
                        par - h_dir[jnp.asarray(sib)])
            else:
                def hist_fn(r, wv, yv, bd):
                    if native:
                        return nat.train_hist_native(Xb_k, r, wv, yv, bd,
                                                     B, C, cls)
                    return _hist_numpy(Xb_k, r, wv, yv, bd, d, B, C, cls)

                if all_direct:
                    hist = hist_fn(rows_g[s0:s1], w_g[s0:s1], y_g[s0:s1],
                                   bch)
                else:
                    h_dir = hist_fn(np.ascontiguousarray(rows_g[sel]),
                                    np.ascontiguousarray(w_g[sel]),
                                    np.ascontiguousarray(y_g[sel]), bnd_d)
                    hist = np.empty((gcc, d, B, C), np.float64)
                    hist[dn] = h_dir
                    par = np.concatenate(parent_rows(), axis=0)
                    hist[dl] = par - hist[der_sib[dl + c0] - c0]

            if has_stash:
                for i in range(i_lo, i_hi + 1):
                    if i not in stash_set:
                        continue
                    o0i, o1i = int(node_off[i]), int(node_off[i + 1])
                    lo, hi = max(o0i, c0), min(o1i, c1)
                    if lo < hi:
                        sl = hist[lo - c0:hi - c0]
                        pend.setdefault(live[i], []).append(
                            sl if use_jax else sl.copy())

            if use_jax:
                res = score_jax(hist, gcc, u_ch, m_ch)
            elif use_f32:
                res = _best_splits(hist.astype(np.float32), msl, cls,
                                   random_split, u_ch, m_ch)
            elif native:
                res = nat.train_best_split_native(hist, msl, cls, u_ch,
                                                  m_ch)
            else:
                res = _best_splits(hist, msl, cls, random_split, u_ch, m_ch)
            (best_gain[c0:c1], best_f[c0:c1], best_b[c0:c1],
             node_tot[c0:c1]) = res

        # ---- split / leaf decisions, vectorized over every tree's nodes ----
        nw = node_tot.sum(1) if cls else node_tot[:, 0]
        if cls:
            pure = node_tot.max(1) >= nw - 1e-9
        else:
            pure = node_tot[:, 2] - node_tot[:, 1] ** 2 \
                / np.maximum(nw, 1e-12) <= 1e-12
        split_g = ~((best_gain <= 1e-12) | (nw < params.min_samples_split)
                    | pure | (depth >= params.max_depth))

        n_split_g = int(split_g.sum())
        if n_split_g:
            if native:
                keep_counts = np.where(split_g, np.diff(bounds_g), 0)
                cpos = (np.cumsum(keep_counts) - keep_counts).astype(np.int64)
                rows_nx, w_nx, child_counts, csum = \
                    nat.train_partition_native(
                        Xb_k, rows_g, w_g, y_g, bounds_g, split_g, best_f,
                        best_b, cpos, int(keep_counts.sum()), cls, Cv)
            else:
                rows_nx, w_nx, child_counts, csum = _partition_numpy(
                    Xb_k, rows_g, w_g, y_g, bounds_g, split_g, best_f,
                    best_b, cls, Cv)
            if cls:
                cvals = csum
            else:
                cvals = np.stack(
                    [csum[:, 0],
                     csum[:, 1] / np.maximum(csum[:, 0], 1e-12)], axis=1)
            ccnt = cvals.sum(1) if cls else cvals[:, 0]
            sr = np.concatenate([[0], np.cumsum(split_g)]).astype(np.int64)

            # ---- early leaf pruning ----
            # Children that can never split — single-instance, weighted
            # count below min_samples_split, or (classification) a single
            # nonzero class in their payload row — are dropped from the
            # next frontier's *sample* set before the histogram pass.  The
            # nodes themselves stay in ``acts`` with zero-width ranges, so
            # per-tree RNG draw counts are unchanged and grown trees stay
            # bit-identical: a zero-sample node scores -inf on every split
            # and becomes the same leaf (its value was already stored from
            # csum above) that a real pass would have produced.  Criteria
            # are exact-safe only: the single-class test is order-robust,
            # and the count test keeps a margin for float summation-order
            # differences vs the next level's histogram totals.
            known_leaf = child_counts <= 1
            known_leaf |= ccnt < params.min_samples_split - 1e-6
            if cls:
                known_leaf |= (csum > 0).sum(axis=1) <= 1
            if _EARLY_PRUNE and known_leaf.any():
                keep_samples = np.repeat(~known_leaf, child_counts)
                rows_nx = np.ascontiguousarray(rows_nx[keep_samples])
                w_nx = np.ascontiguousarray(w_nx[keep_samples])
                child_counts = np.where(known_leaf, 0, child_counts)

        new_live = []
        new_ret_h: dict = {}
        new_ret_kl: dict = {}
        for i, t in enumerate(live):
            o0, o1 = int(node_off[i]), int(node_off[i + 1])
            st = stores[t]
            sp = split_g[o0:o1]
            ns = int(sp.sum())
            if not ns:
                # every active node became a leaf; unresolved feat (-2)
                # entries are converted at assembly
                acts[t] = np.empty(0, np.int64)
                pend.pop(t, None)
                continue
            a_s = acts[t][sp]
            f_s = best_f[o0:o1][sp]
            b_s = best_b[o0:o1][sp]
            base = st.alloc(2 * ns)
            st.feat[a_s] = f_s
            st.thr[a_s] = binner.thresholds(f_s, b_s)
            cid = base + np.arange(2 * ns, dtype=np.int64)
            st.left[a_s] = cid[0::2]
            st.right[a_s] = cid[1::2]
            st.last_level = depth
            s_lo, s_hi = int(sr[o0]), int(sr[o1])
            st.val[base:base + 2 * ns] = \
                cvals[2 * s_lo:2 * s_hi].astype(np.float32)
            st.cnt[base:base + 2 * ns] = ccnt[2 * s_lo:2 * s_hi]
            parts = pend.pop(t, None)
            if parts is not None:
                # retain this level's split-node histograms (split-rank
                # rows) + the children's known-leaf flags for next level's
                # sibling subtraction
                full_h = parts[0] if len(parts) == 1 else (
                    jnp.concatenate(parts, axis=0) if use_jax
                    else np.concatenate(parts, axis=0))
                new_ret_h[t] = full_h[np.flatnonzero(sp)]
                new_ret_kl[t] = known_leaf[2 * s_lo:2 * s_hi].copy()
            acts[t] = cid
            new_live.append(t)
        live = new_live
        ret_hist, ret_kl = new_ret_h, new_ret_kl
        if n_split_g:
            # partition output IS the next level's global frontier layout
            rows_g, w_g = rows_nx, w_nx
            bounds_g = np.concatenate(
                [[0], np.cumsum(child_counts)]).astype(np.int64)
        else:
            rows_g = np.empty(0, np.int64)
            w_g = np.empty(0, np.float64)
            bounds_g = np.zeros(1, np.int64)
        _h_level.observe(time.perf_counter() - _t_level)
        _c_levels.inc()

    return [st.to_tree() for st in stores]
