"""Routing benchmark: seed per-tree loop vs batched backends.

  PYTHONPATH=src:. python -m benchmarks.bench_routing [--n 50000] [--d 20]
      [--trees 100] [--out BENCH_routing.json]

Measures ``BaseForest.apply`` wall-clock through four paths on the same
fitted forest:

  seed_loop      route_forest_numpy — serial Python loop over trees
  batched_numpy  route_forest_batched(backend="numpy") — one vectorized
                 active-lane pass
  native         route_forest_batched(backend="native") — lazily-compiled C
                 kernel (what backend="auto", the apply default, selects
                 when a host compiler exists)
  jax            route_forest_batched(backend="jax") — jit'd vmap routing
                 (float32: a tiny fraction of threshold-straddling lanes may
                 legally differ; the report records that fraction)

and emits a JSON report with per-path seconds and speedups over the seed
loop.  The acceptance bar for this repo is apply (= auto backend) >= 5x
seed_loop at (50k x 20, 100 trees).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.compile_cache import configure_compile_cache
from repro.data.synthetic import gaussian_classes
from repro.forest.ensemble import RandomForest
from repro.forest.trees import route_forest_batched, route_forest_numpy


def _time(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(n: int = 50_000, d: int = 20, trees: int = 100,
        out_path: str = "BENCH_routing.json", repeats: int = 3) -> dict:
    X, y = gaussian_classes(n, d=d, n_classes=4, seed=0)

    t0 = time.perf_counter()
    rf = RandomForest(n_trees=trees, seed=0).fit(X, y)
    fit_s = time.perf_counter() - t0
    ta = rf.tree_arrays()
    print(f"fit: {fit_s:.2f}s  (T={trees}, max_depth={ta.max_depth}, "
          f"L={ta.total_leaves})", flush=True)

    results = {}
    notes = {}
    expected = route_forest_numpy(rf.trees_, X)
    results["seed_loop"] = _time(lambda: route_forest_numpy(rf.trees_, X),
                                 repeats)
    print(f"seed_loop:     {results['seed_loop']:.3f}s", flush=True)

    got = route_forest_batched(ta, X, backend="numpy")
    assert np.array_equal(got, expected), "batched numpy mismatch"
    results["batched_numpy"] = _time(
        lambda: route_forest_batched(ta, X, backend="numpy"), repeats)
    print(f"batched_numpy: {results['batched_numpy']:.3f}s", flush=True)

    from repro.forest import _native
    if _native.available():
        got = route_forest_batched(ta, X, backend="native")
        assert np.array_equal(got, expected), "native routing mismatch"
        results["native"] = _time(
            lambda: route_forest_batched(ta, X, backend="native"), repeats)
        print(f"native:        {results['native']:.3f}s", flush=True)
    else:
        print("native backend skipped: no host C compiler", flush=True)

    try:
        got = route_forest_batched(ta, X, backend="jax")   # compile warm-up
        # float32 routing may legally flip lanes whose value straddles the
        # float32 rounding of a threshold; anything beyond that is a bug.
        mismatch = float((got != expected).mean())
        assert mismatch < 1e-4, f"jax mismatch fraction {mismatch}"
        notes["jax_f32_mismatch_fraction"] = mismatch
        results["jax"] = _time(
            lambda: route_forest_batched(ta, X, backend="jax"), repeats)
        print(f"jax:           {results['jax']:.3f}s "
              f"(f32 mismatch frac {mismatch:.2e})", flush=True)
    except Exception as exc:                               # jax unavailable
        print(f"jax backend skipped: {exc}", flush=True)

    report = {
        "config": {"n": n, "d": d, "trees": trees,
                   "max_depth": int(ta.max_depth),
                   "total_leaves": int(ta.total_leaves),
                   "fit_seconds": round(fit_s, 3), "repeats": repeats,
                   "apply_default_backend":
                       "native" if "native" in results else "numpy"},
        "seconds": {k: round(v, 4) for k, v in results.items()},
        "speedup_vs_seed_loop": {
            k: round(results["seed_loop"] / v, 2)
            for k, v in results.items() if k != "seed_loop"},
        "notes": notes,
    }
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    print(json.dumps(report["speedup_vs_seed_loop"], indent=2), flush=True)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--d", type=int, default=20)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="BENCH_routing.json")
    args = ap.parse_args()
    configure_compile_cache()
    run(n=args.n, d=args.d, trees=args.trees, out_path=args.out,
        repeats=args.repeats)


if __name__ == "__main__":
    main()
