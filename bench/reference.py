"""Plain numpy reference of the forest proximities the benchmark checks.

It takes the fitted trees as the model (split feature, threshold and
children of every node), the in-bag counts of the bootstrap, the
ensemble's tree weights, and the training rows and labels, and computes
everything else itself: which node each row reaches, the leaves' in-bag
masses, the query and reference weights of the proximity, and the
products.  It imports nothing of the program and touches none of its
routing, weights, factors or kernels.

Proximity of query x and training row j (the SWLC form of the paper):

    P(x, j) = sum_t q_t(x) w_t(j) [leaf_t(x) == leaf_t(j)]

The configuration's ``kernel_method`` names the weight assignment,
``bench/weights/<kernel_method>.py``, which provides
``reference_weights(model, t, leaf)`` -> w_t(j) of every training row and
``query_weights(model, t, leaf)`` -> q_t(x) of each query row, given the
node each row reaches in tree t; ``model`` is the ``Model`` record below.
``precision`` picks how the products are computed: ``float64`` is the
reference; ``float32`` and ``bfloat16`` round every operand, table entry
and product to that type, sum in float64 and round the answer to that type.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from . import lookup

__all__ = ["Model", "ReferenceForest"]


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


@dataclasses.dataclass
class Model:
    """The fitted ensemble as a weight assignment reads it, in numpy
    arrays."""
    trees: list                  # (feature, threshold, left, right) per tree
    inbag: np.ndarray            # (T, N) in-bag counts, float64
    tree_weights: Optional[np.ndarray]   # (T,); None: the ensemble has none
    y: np.ndarray                # (N,) training labels, as given
    n_nodes: np.ndarray          # (T,) nodes of each tree
    train_leaf: list             # T x (N,) node each training row reaches


class ReferenceForest:
    """The reference side of one fitted forest: training rows routed, leaf
    members and masses counted, ready for out-of-sample queries."""

    def __init__(self, trees, inbag: np.ndarray, X_train: np.ndarray,
                 y_train: np.ndarray, kernel_method: str,
                 n_threads: int = 0, tree_weights=None):
        self.weights = lookup.load("weights", kernel_method)
        self.trees = [(np.asarray(t.feature, dtype=np.int64),
                       np.asarray(t.threshold),
                       np.asarray(t.left, dtype=np.int64),
                       np.asarray(t.right, dtype=np.int64)) for t in trees]
        self.T = len(self.trees)
        self.y = np.asarray(y_train, dtype=np.int64)
        self.n_threads = n_threads or os.cpu_count() or 1
        self.model = Model(
            trees=self.trees, inbag=np.asarray(inbag, dtype=np.float64),
            tree_weights=(None if tree_weights is None else
                          np.array(tree_weights, dtype=np.float64)),
            y=np.asarray(y_train),
            n_nodes=np.array([len(f) for f, *_ in self.trees], dtype=np.int64),
            train_leaf=self._map(lambda t: route(*self.trees[t], X_train)))
        self.w = [self.weights.reference_weights(
            self.model, t, self.model.train_leaf[t]) for t in range(self.T)]

    def _map(self, fn):
        with ThreadPoolExecutor(self.n_threads) as ex:
            return list(ex.map(fn, range(self.T)))

    def query_nodes(self, X: np.ndarray) -> list:
        return self._map(lambda t: route(*self.trees[t], X))

    # -------------------------------------------------------------- ops --
    def predict(self, X: np.ndarray, n_classes: int,
                precision: str = "float64") -> np.ndarray:
        """(n, C) class scores sum_j P(x, j) [y_j == c]."""
        store = _precision(precision)
        qn = self.query_nodes(X)
        out = np.zeros((X.shape[0], n_classes))
        for t in range(self.T):
            nd = self.model.train_leaf[t]
            w = self.w[t].astype(store).astype(np.float64)
            S = np.bincount(nd * n_classes + self.y, weights=w,
                            minlength=self.model.n_nodes[t] * n_classes)
            S = S.reshape(-1, n_classes).astype(store)
            q = self.weights.query_weights(self.model, t, qn[t]).astype(store)
            out += (q[:, None] * S[qn[t]]).astype(np.float64)
        return out.astype(store).astype(np.float64)
