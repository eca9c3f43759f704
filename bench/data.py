"""Synthetic data in the shapes of the benchmark's public data sets.

The deployment's model — the generator's parameters ("world"), the
training rows and the forest's own seed — is fixed by the configuration's
``model_seed``: every run fits the same forest, so every run does the same
work.  The query rows the window sends are drawn from ``--seed``.

The configuration's ``generator`` names ``bench/generators/<generator>.py``,
which provides ``world(cfg) -> dict`` and ``rows(cfg, world, y, rng) -> X``,
and may provide ``labels(cfg, n, rng) -> y`` (by default a class draw from
``class_counts``).
"""
from __future__ import annotations

import numpy as np

from . import lookup

__all__ = ["seed_rng", "forest_seed", "class_labels", "training_rows",
           "query_rows"]

_STREAMS = {"world": 1, "train": 2, "query": 3, "sample": 4, "forest": 5}


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """Generator for one named stream of ``seed`` (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 63), _STREAMS[stream]]))


def forest_seed(cfg: dict) -> int:
    """The forest's own seed (bootstrap draws, feature subsets)."""
    return int(seed_rng(cfg["model_seed"], "forest").integers(0, 1 << 62))


def class_labels(cfg: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` classes drawn in the proportions of ``class_counts``."""
    p = np.asarray(cfg["class_counts"], dtype=np.float64)
    return rng.choice(len(p), size=n, p=p / p.sum()).astype(np.int64)


def _rows(cfg: dict, n: int, rng: np.random.Generator) -> tuple:
    gen = lookup.load("generators", cfg["generator"])
    y = getattr(gen, "labels", class_labels)(cfg, n, rng)
    return gen.rows(cfg, gen.world(cfg), y, rng), y


def training_rows(cfg: dict):
    """(X, y) of the configuration's ``n_train`` training rows."""
    return _rows(cfg, cfg["n_train"], seed_rng(cfg["model_seed"], "train"))


def query_rows(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` held-out rows from the training rows' distribution."""
    return _rows(cfg, n, seed_rng(seed, "query"))[0]
