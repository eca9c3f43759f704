"""RF-GAP (Rhodes et al. 2023) for an out-of-sample query: q_t = 1/T and
w_t(j) = c_t(j) / max(1, sum of c_t over j's leaf), where c_t(j) is the
in-bag count of training row j in tree t."""
import numpy as np


def reference_weights(model, t: int, leaf: np.ndarray) -> np.ndarray:
    c = model.inbag[t]
    mass = np.bincount(leaf, weights=c, minlength=model.n_nodes[t])
    return c / np.maximum(mass[leaf], 1.0)


def query_weights(model, t: int, leaf: np.ndarray) -> np.ndarray:
    return np.full(len(leaf), 1.0 / len(model.trees))
