
"""Boosted proximity (Tan et al. 2020): q_t = w_t = sqrt(w_t / sum_s w_s)."""
import numpy as np


def reference_weights(model, t, leaf):
    tw = model.tree_weights
    return np.full(len(leaf), np.sqrt(tw[t] / tw.sum()))


query_weights = reference_weights
