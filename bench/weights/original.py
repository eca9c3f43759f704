"""Breiman's original proximity: q_t = w_t = 1/sqrt(T)."""
import numpy as np


def reference_weights(model, t: int, leaf: np.ndarray) -> np.ndarray:
    return np.full(len(leaf), 1.0 / np.sqrt(len(model.trees)))


def query_weights(model, t: int, leaf: np.ndarray) -> np.ndarray:
    return np.full(len(leaf), 1.0 / np.sqrt(len(model.trees)))
