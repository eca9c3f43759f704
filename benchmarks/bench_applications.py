"""Proximity-applications benchmark: factored vs dense-oracle, plus the
50k-sample headline numbers for imputation and outlier scoring.

  PYTHONPATH=src:. python -m benchmarks.bench_applications
      [--n 50000] [--d 20] [--trees 50] [--out BENCH_applications.json]

Two experiments:

1. **crossover grid** — outlier scores through the factored engine
   (streamed squared row sums) vs the dense oracle (materialize P = Q Wᵀ
   densely, then square/sum).  Reports per-size seconds and the first grid
   size where the factored path wins; dense is skipped once its P would
   exceed ``--dense-cap-gb``.
2. **headline at --n** — outlier scores and one proximity-weighted
   imputation sweep (rough fill → fit → proximity update) at full size,
   factored only (the dense oracle is far past memory there: a 50k dense P
   alone is 20 GB).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.compile_cache import configure_compile_cache
from repro.applications.imputation import ProximityImputer
from repro.applications.outliers import outlier_scores
from repro.core.api import ForestKernel
from repro.data.synthetic import gaussian_classes


def _time(fn, repeats: int = 3):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _dense_outliers(fk: ForestKernel, y: np.ndarray) -> np.ndarray:
    """The dense oracle: materialize P, then within-class squared sums."""
    P = np.asarray((fk.Q_ @ fk.W_.T).todense())
    n_classes = int(y.max()) + 1
    counts = np.bincount(y, minlength=n_classes).astype(np.float64)
    own = np.empty(len(y))
    for c in range(n_classes):
        m = y == c
        own[m] = (P[np.ix_(m, m)] ** 2).sum(axis=1)
    with np.errstate(divide="ignore", over="ignore"):
        raw = counts[y] / np.maximum(own, np.finfo(np.float64).tiny)
    return np.minimum(raw, float(len(y)) ** 2)


def run(n: int = 50_000, d: int = 20, trees: int = 50, repeats: int = 3,
        grid=(1000, 2000, 4000, 8000), impute_iters: int = 2,
        dense_cap_gb: float = 4.0,
        out_path: str = "BENCH_applications.json") -> dict:
    report = {"config": {"n": n, "d": d, "trees": trees, "repeats": repeats,
                         "grid": list(grid), "impute_iters": impute_iters}}

    # ---- crossover grid: factored vs dense-oracle outlier scores ----
    cross = []
    crossover_n = None
    for gn in grid:
        X, y = gaussian_classes(gn, d=d, n_classes=4, seed=0)
        fk = ForestKernel(kernel_method="gap", n_trees=trees, seed=0)
        fk.fit(X, y)
        entry = {"n": gn}
        t_fact, s_fact = _time(lambda: outlier_scores(fk.engine, y,
                                                      normalize=False),
                               repeats)
        entry["factored_s"] = round(t_fact, 4)
        if 8 * gn * gn <= dense_cap_gb * (1 << 30):
            t_dense, s_dense = _time(lambda: _dense_outliers(fk, y), repeats)
            entry["dense_s"] = round(t_dense, 4)
            entry["speedup"] = round(t_dense / t_fact, 2)
            np.testing.assert_allclose(s_fact, s_dense, rtol=1e-8)
            if crossover_n is None and t_fact < t_dense:
                crossover_n = gn
        else:
            entry["dense_s"] = None
        cross.append(entry)
        print(f"n={gn:>6}: factored {entry['factored_s']}s  "
              f"dense {entry['dense_s']}s", flush=True)
    report["outliers_crossover"] = {"grid": cross,
                                    "factored_wins_from_n": crossover_n}

    # ---- headline at full size (factored only) ----
    X, y = gaussian_classes(n, d=d, n_classes=4, seed=0)
    t0 = time.perf_counter()
    fk = ForestKernel(kernel_method="gap", n_trees=trees, seed=0)
    fk.fit(X, y)
    fit_s = time.perf_counter() - t0
    t_out, _ = _time(lambda: outlier_scores(fk.engine, y), repeats)
    print(f"headline n={n}: fit {fit_s:.1f}s, outlier_scores {t_out:.2f}s",
          flush=True)

    Xm = X.copy()
    rng = np.random.default_rng(0)
    mask = rng.random(Xm.shape) < 0.05
    Xm[mask] = np.nan
    t0 = time.perf_counter()
    imp = ProximityImputer(
        n_iter=impute_iters,
        kernel_kwargs=dict(kernel_method="gap", n_trees=trees, seed=0))
    imp.fit_transform(Xm, y)
    t_imp = time.perf_counter() - t0
    err = float(np.abs(imp.X_imputed_[mask] - X[mask]).mean())
    med = np.nanmedian(Xm, axis=0)
    err_med = float(np.abs(np.broadcast_to(med, Xm.shape)[mask]
                           - X[mask]).mean())
    print(f"imputation ({impute_iters} iters incl. refits): {t_imp:.1f}s, "
          f"mae {err:.3f} vs median-fill {err_med:.3f}", flush=True)
    report["headline"] = {
        "fit_s": round(fit_s, 2),
        "outlier_scores_s": round(t_out, 3),
        "impute_s": round(t_imp, 2),
        "impute_mae": round(err, 4),
        "median_fill_mae": round(err_med, 4),
        "missing_entries": int(mask.sum()),
    }

    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    print(json.dumps(report["headline"], indent=2), flush=True)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--d", type=int, default=20)
    ap.add_argument("--trees", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--grid", default="1000,2000,4000,8000")
    ap.add_argument("--impute-iters", type=int, default=2)
    ap.add_argument("--dense-cap-gb", type=float, default=4.0)
    ap.add_argument("--out", default="BENCH_applications.json")
    args = ap.parse_args()
    configure_compile_cache()
    run(n=args.n, d=args.d, trees=args.trees, repeats=args.repeats,
        grid=tuple(int(g) for g in args.grid.split(",")),
        impute_iters=args.impute_iters, dense_cap_gb=args.dense_cap_gb,
        out_path=args.out)


if __name__ == "__main__":
    main()
