"""UCI Covertype (Blackard & Dean 1999): 10 integer-valued cartographic
variables, a 4-way one-hot wilderness area and a 40-way one-hot soil type;
7 cover types.  Ranges of the continuous columns as in the UCI description
(elevation m, aspect deg, slope deg, distances m, hillshade index 0-255).
Labels: the default class draw from ``class_counts``."""
import numpy as np

from bench.data import seed_rng

_COV_RANGES = np.array([
    [1859, 3858], [0, 360], [0, 66], [0, 1397], [-173, 601],
    [0, 7117], [0, 254], [0, 254], [0, 254], [0, 7173]], dtype=np.float64)


def world(cfg: dict) -> dict:
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


def rows(cfg: dict, world: dict, y: np.ndarray,
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
