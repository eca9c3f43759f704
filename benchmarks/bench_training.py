"""Tree-training benchmark: numpy trainer vs native kernels vs batched.

  PYTHONPATH=src:. python -m benchmarks.bench_training [--n 50000] [--d 20]
      [--trees 100] [--out BENCH_training.json]

Measures forest fit wall-clock through three paths on identical data
(and verifies all three grow bit-identical trees):

  numpy           tree_backend="numpy" — tiled-bincount histograms +
                  vectorized scoring, thread-pool over trees (n_jobs auto)
  native          per-tree native C kernels (train_level / train_partition),
                  trees grown one at a time (tree_block=1)
  native_batched  the default native path: every level is ONE native call
                  spanning all trees' frontiers (what tree_backend="auto"
                  selects when a host compiler exists)

and emits a JSON report with per-path seconds and speedups over the numpy
trainer.  The acceptance bar for this repo is native_batched >= 4x numpy at
(50k x 20, 100 trees).

When jax is importable, a reduced-size ``jax`` section is also measured:
``tree_backend="jax"`` routes every level's histogram through the device
kernels (pallas on TPU/GPU, the XLA scatter-add reference on CPU) and the
trees are asserted bit-identical to the numpy trainer under x64 scoring.
On a CPU-only host this times the *reference* device path — the number is
a dispatch-overhead floor, not an accelerator result.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.compile_cache import configure_compile_cache
from repro.data.synthetic import gaussian_classes
from repro.forest import _native
from repro.forest.ensemble import RandomForest


def _trees_equal(a, b) -> bool:
    fields = ["feature", "threshold", "left", "right", "leaf_id", "value",
              "n_node_samples"]
    return len(a) == len(b) and all(
        np.array_equal(getattr(t1, f), getattr(t2, f))
        for t1, t2 in zip(a, b) for f in fields)


def _bench_jax(n: int, d: int, trees: int) -> dict | None:
    """Reduced-config jax-backend timing with a numpy conformance assert."""
    try:
        import jax
    except Exception:
        print("jax path skipped: jax not importable", flush=True)
        return None
    old_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        X, y = gaussian_classes(n, d=d, n_classes=4, seed=0)

        def fit(backend):
            return RandomForest(n_trees=trees, seed=0,
                                tree_backend=backend).fit(X, y)

        t0 = time.perf_counter()
        f_np = fit("numpy")
        s_np = round(time.perf_counter() - t0, 3)
        fit("jax")                                   # warm compile caches
        t0 = time.perf_counter()
        f_jx = fit("jax")
        s_jx = round(time.perf_counter() - t0, 3)
        assert _trees_equal(f_np.trees_, f_jx.trees_), \
            "jax trees differ from numpy trainer"
        dev = jax.devices()[0].platform
        print(f"jax ({dev}):      {s_jx:.2f}s  (numpy at this size: "
              f"{s_np:.2f}s)", flush=True)
        return {"config": {"n": n, "d": d, "trees": trees, "device": dev,
                           "conformance": "bit-identical to numpy (asserted, "
                                          "x64 scoring)"},
                "fit_seconds": {"numpy": s_np, "jax": s_jx}}
    finally:
        jax.config.update("jax_enable_x64", old_x64)


def run(n: int = 50_000, d: int = 20, trees: int = 100,
        out_path: str = "BENCH_training.json", repeats: int = 1,
        jax_n: int = 8_000, jax_trees: int = 20) -> dict:
    X, y = gaussian_classes(n, d=d, n_classes=4, seed=0)

    def fit(backend: str, tree_block: int = 0):
        # tree_block=1 -> per-tree native (same kernels, no batching)
        return RandomForest(n_trees=trees, seed=0, tree_backend=backend,
                            tree_block=tree_block).fit(X, y)

    results, forests = {}, {}
    t0 = time.perf_counter()
    forests["numpy"] = fit("numpy")
    results["numpy"] = round(time.perf_counter() - t0, 3)
    print(f"numpy:          {results['numpy']:.2f}s", flush=True)

    if _native.available():
        for name, block in [("native", 1), ("native_batched", 0)]:
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                forests[name] = fit("native", tree_block=block)
                best = min(best, time.perf_counter() - t0)
            results[name] = round(best, 3)
            print(f"{name + ':':15s} {results[name]:.2f}s", flush=True)
            assert _trees_equal(forests["numpy"].trees_,
                                forests[name].trees_), \
                f"{name} trees differ from numpy trainer"
    else:
        print("native paths skipped: no host C compiler", flush=True)

    ta = forests["numpy"].tree_arrays()
    report = {
        "config": {"n": n, "d": d, "trees": trees,
                   "max_depth": int(ta.max_depth),
                   "total_leaves": int(ta.total_leaves),
                   "repeats": repeats,
                   "conformance": "all paths bit-identical (asserted)"},
        "fit_seconds": results,
        "speedup_vs_numpy": {k: round(results["numpy"] / v, 2)
                             for k, v in results.items() if k != "numpy"},
    }
    jax_report = _bench_jax(jax_n, d, jax_trees)
    if jax_report is not None:
        report["jax"] = jax_report
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")
    print(json.dumps(report, indent=2), flush=True)
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--d", type=int, default=20)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--jax-n", type=int, default=8_000)
    ap.add_argument("--jax-trees", type=int, default=20)
    ap.add_argument("--out", type=str, default="BENCH_training.json")
    a = ap.parse_args()
    configure_compile_cache()
    run(n=a.n, d=a.d, trees=a.trees, out_path=a.out, repeats=a.repeats,
        jax_n=a.jax_n, jax_trees=a.jax_trees)
