"""Synthetic data in the shapes of the benchmark's public data sets.

The deployment's model — the class-conditional parameters ("world"), the
training rows and the forest's own seed — is fixed by the configuration's
``model_seed``: every run fits the same forest, so every run does the same
work.  The query rows the window sends are drawn from ``--seed``.

Generators are looked up by the ``generator`` key of a configuration file.
"""
from __future__ import annotations

import numpy as np

__all__ = ["GENERATORS", "seed_rng", "training_rows", "query_rows"]

_STREAMS = {"world": 1, "train": 2, "query": 3, "sample": 4, "forest": 5}


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """Generator for one named stream of ``seed`` (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 63), _STREAMS[stream]]))


def forest_seed(cfg: dict) -> int:
    """The forest's own seed (bootstrap draws, feature subsets)."""
    return int(seed_rng(cfg["model_seed"], "forest").integers(0, 1 << 62))


# ------------------------------------------------------------- Covertype --
# UCI Covertype (Blackard & Dean 1999): 10 integer-valued cartographic
# variables, a 4-way one-hot wilderness area and a 40-way one-hot soil
# type; 7 cover types.  Ranges of the continuous columns as in the UCI
# description (elevation m, aspect deg, slope deg, distances m, hillshade
# index 0-255).
_COV_RANGES = np.array([
    [1859, 3858], [0, 360], [0, 66], [0, 1397], [-173, 601],
    [0, 7117], [0, 254], [0, 254], [0, 254], [0, 7173]], dtype=np.float64)


def _covertype_world(cfg: dict) -> dict:
    rng = seed_rng(cfg["model_seed"], "world")
    C = cfg["n_classes"]
    sep = cfg["assumed"]["class_separation"]
    return {
        "mean": rng.normal(0.0, sep, size=(C, cfg["n_continuous"])),
        "wild": rng.dirichlet(np.full(cfg["n_wilderness"],
                                      cfg["assumed"]["wilderness_alpha"]),
                              size=C),
        "soil": rng.dirichlet(np.full(cfg["n_soil"],
                                      cfg["assumed"]["soil_alpha"]), size=C),
    }


def _covertype(cfg: dict, world: dict, y: np.ndarray,
               rng: np.random.Generator) -> np.ndarray:
    n = len(y)
    nc, nw, ns = cfg["n_continuous"], cfg["n_wilderness"], cfg["n_soil"]
    z = world["mean"][y] + rng.normal(size=(n, nc))
    lo, hi = _COV_RANGES[:, 0], _COV_RANGES[:, 1]
    # a standard normal mapped so that +-3 sigma spans the UCI range
    cont = np.clip(np.rint(lo + (hi - lo) * (z + 3.0) / 6.0), lo, hi)
    X = np.zeros((n, nc + nw + ns))
    X[:, :nc] = cont
    u = rng.random((n, 2))
    wild = (np.cumsum(world["wild"][y], axis=1) < u[:, :1]).sum(axis=1)
    soil = (np.cumsum(world["soil"][y], axis=1) < u[:, 1:]).sum(axis=1)
    X[np.arange(n), nc + np.minimum(wild, nw - 1)] = 1.0
    X[np.arange(n), nc + nw + np.minimum(soil, ns - 1)] = 1.0
    return X


# ----------------------------------------------------------------- HIGGS --
# UCI HIGGS (Baldi, Sadowski & Whiteson 2014): 21 low-level kinematic
# features (lepton pT/eta/phi, missing energy magnitude/phi, four jets of
# pT/eta/phi/b-tag) and 7 high-level invariant masses; signal vs background.
_BTAG = np.array([0.0, 1.0865, 2.1730])


def _higgs_world(cfg: dict) -> dict:
    rng = seed_rng(cfg["model_seed"], "world")
    a = cfg["assumed"]
    return {
        # per class: log-scale shifts of the 5 pT-like and 7 mass columns
        "pt_shift": rng.normal(0.0, a["class_separation"], size=(2, 5)),
        "mass_shift": rng.normal(0.0, a["class_separation"], size=(2, 7)),
        "btag_p": np.array([[0.70, 0.15, 0.15], [0.55, 0.20, 0.25]]),
    }


def _higgs(cfg: dict, world: dict, y: np.ndarray,
           rng: np.random.Generator) -> np.ndarray:
    n = len(y)
    pt = np.exp(rng.normal(-0.1, 0.45, size=(n, 5)) + world["pt_shift"][y])
    eta = rng.normal(0.0, 1.0, size=(n, 5)) * np.where(y == 1, 0.95, 1.0)[:, None]
    phi = rng.uniform(-np.pi, np.pi, size=(n, 5))
    u = rng.random((n, 4))
    btag = _BTAG[(np.cumsum(world["btag_p"][y], axis=1)[:, None, :]
                  < u[:, :, None]).sum(axis=2).clip(0, 2)]
    # masses correlate with the summed jet pT, as invariant masses do
    mass = np.exp(rng.normal(0.0, 0.3, size=(n, 7)) + world["mass_shift"][y]
                  + 0.3 * np.log(pt[:, 1:].sum(axis=1, keepdims=True) / 4.0))
    X = np.empty((n, 28))
    X[:, 0], X[:, 1], X[:, 2] = pt[:, 0], eta[:, 0], phi[:, 0]   # lepton
    X[:, 3] = np.abs(rng.normal(1.0, 0.6, n))                     # missing E
    X[:, 4] = rng.uniform(-np.pi, np.pi, n)
    for j in range(4):                                            # jets
        X[:, 5 + 4 * j] = pt[:, 1 + j]
        X[:, 6 + 4 * j] = eta[:, 1 + j]
        X[:, 7 + 4 * j] = phi[:, 1 + j]
        X[:, 8 + 4 * j] = btag[:, j]
    X[:, 21:] = mass
    return X


GENERATORS = {
    "covertype": (_covertype_world, _covertype),
    "higgs": (_higgs_world, _higgs),
}


def _labels(cfg: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    p = np.asarray(cfg["class_counts"], dtype=np.float64)
    return rng.choice(len(p), size=n, p=p / p.sum()).astype(np.int64)


def training_rows(cfg: dict):
    """(X, y) of the configuration's ``n_train`` training rows."""
    world_fn, rows_fn = GENERATORS[cfg["generator"]]
    rng = seed_rng(cfg["model_seed"], "train")
    y = _labels(cfg, cfg["n_train"], rng)
    return rows_fn(cfg, world_fn(cfg), y, rng), y


def query_rows(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` held-out rows from the training rows' distribution."""
    world_fn, rows_fn = GENERATORS[cfg["generator"]]
    rng = seed_rng(seed, "query")
    y = _labels(cfg, n, rng)
    return rows_fn(cfg, world_fn(cfg), y, rng)
