"""Plain numpy reference of the forest proximities the benchmark checks.

It takes the fitted trees as the model (split feature, threshold and
children of every node), the in-bag counts of the bootstrap, and the
training rows and labels, and computes everything else itself: which node
each row reaches, the leaves' in-bag masses, the query and reference
weights of the proximity, and the products.  It imports nothing of the
program and touches none of its routing, weights, factors or kernels.

Proximity of query x and training row j (the SWLC form of the paper):

    P(x, j) = sum_t q_t(x) w_t(j) [leaf_t(x) == leaf_t(j)]

with, for an out-of-sample x,

    original   q_t = w_t = 1/sqrt(T)
    gap        q_t = 1/T,  w_t(j) = c_t(j) / max(1, sum of c_t over the leaf)

where c_t(j) is the in-bag count of row j in tree t.  ``precision`` picks
how the products are computed: ``float64`` is the reference; ``float32``
and ``bfloat16`` round every operand, table entry and product to that type,
sum in float64 and round the answer to that type.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = ["ReferenceForest"]


def _precision(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    if name in ("float64", "float32"):
        return np.dtype(name).type
    raise ValueError(f"unknown precision {name!r}")


def route(feature, threshold, left, right, X: np.ndarray) -> np.ndarray:
    """Node reached by every row of X in one tree (x[f] <= thr goes left)."""
    node = np.zeros(X.shape[0], dtype=np.int64)
    idx = np.arange(X.shape[0])
    thr = np.asarray(threshold, dtype=np.float64)
    while idx.size:
        nd = node[idx]
        f = feature[nd]
        inner = f >= 0
        idx, nd, f = idx[inner], nd[inner], f[inner]
        if not idx.size:
            break
        go_left = X[idx, f] <= thr[nd]
        node[idx] = np.where(go_left, left[nd], right[nd])
    return node


class ReferenceForest:
    """The reference side of one fitted forest: training rows routed, leaf
    members and masses counted, ready for out-of-sample queries."""

    def __init__(self, trees, inbag: np.ndarray, X_train: np.ndarray,
                 y_train: np.ndarray, kernel_method: str,
                 n_threads: int = 0):
        if kernel_method not in ("original", "gap"):
            raise ValueError(f"no reference for kernel {kernel_method!r}")
        self.trees = [(np.asarray(t.feature, dtype=np.int64),
                       np.asarray(t.threshold),
                       np.asarray(t.left, dtype=np.int64),
                       np.asarray(t.right, dtype=np.int64)) for t in trees]
        self.T = len(self.trees)
        self.n = X_train.shape[0]
        self.y = np.asarray(y_train, dtype=np.int64)
        self.method = kernel_method
        self.inbag = np.asarray(inbag, dtype=np.float64)       # (T, N)
        self.n_threads = n_threads or os.cpu_count() or 1
        nodes = self._map(lambda t: route(*self.trees[t], X_train))
        self.train_node = nodes                                 # T x (N,)
        self.w = [self._ref_weights(t) for t in range(self.T)]

    def _map(self, fn):
        with ThreadPoolExecutor(self.n_threads) as ex:
            return list(ex.map(fn, range(self.T)))

    def _ref_weights(self, t: int) -> np.ndarray:
        nd = self.train_node[t]
        if self.method == "original":
            return np.full(self.n, 1.0 / np.sqrt(self.T))
        c = self.inbag[t]
        mass = np.bincount(nd, weights=c, minlength=len(self.trees[t][0]))
        return c / np.maximum(mass[nd], 1.0)

    def _q(self) -> float:
        return 1.0 / self.T if self.method == "gap" else 1.0 / np.sqrt(self.T)

    def query_nodes(self, X: np.ndarray) -> list:
        return self._map(lambda t: route(*self.trees[t], X))

    # -------------------------------------------------------------- ops --
    def predict(self, X: np.ndarray, n_classes: int,
                precision: str = "float64") -> np.ndarray:
        """(n, C) class scores sum_j P(x, j) [y_j == c]."""
        store = _precision(precision)
        q = np.asarray(self._q(), dtype=store)
        qn = self.query_nodes(X)
        out = np.zeros((X.shape[0], n_classes))
        for t in range(self.T):
            nd = self.train_node[t]
            w = self.w[t].astype(store).astype(np.float64)
            S = np.bincount(nd * n_classes + self.y, weights=w,
                            minlength=len(self.trees[t][0]) * n_classes)
            S = S.reshape(-1, n_classes).astype(store)
            out += (q * S[qn[t]]).astype(np.float64)
        return out.astype(store).astype(np.float64)
