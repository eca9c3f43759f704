"""UCI HIGGS (Baldi, Sadowski & Whiteson 2014): 21 low-level kinematic
features (lepton pT/eta/phi, missing energy magnitude/phi, four jets of
pT/eta/phi/b-tag) and 7 high-level invariant masses; signal vs background.
Labels: the default class draw from ``class_counts``."""
import numpy as np

from bench.data import seed_rng

_BTAG = np.array([0.0, 1.0865, 2.1730])


def world(cfg: dict) -> dict:
    rng = seed_rng(cfg["model_seed"], "world")
    a = cfg["assumed"]
    return {
        # per class: log-scale shifts of the 5 pT-like and 7 mass columns
        "pt_shift": rng.normal(0.0, a["class_separation"], size=(2, 5)),
        "mass_shift": rng.normal(0.0, a["class_separation"], size=(2, 7)),
        "btag_p": np.array([[0.70, 0.15, 0.15], [0.55, 0.20, 0.25]]),
    }


def rows(cfg: dict, world: dict, y: np.ndarray,
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
