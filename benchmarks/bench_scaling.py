"""Paper Fig 4.2 / Fig H.1 scaling curves + the out-of-core headline run.

Two modes:

**Curves** (default) — near-linear time & memory scaling of exact kernel
computation with sample size, across proximity definitions, forest types,
leaf sizes and depths.  Reported cost = cache construction + query/reference
maps + full sparse kernel (forest training excluded, matching the paper's
protocol); slopes come from log-log regression (claim: slope ≈ 1).

**Out-of-core** (``--out-of-core``) — the repo's headline scaling row: a
disk-resident end-to-end pipeline (streamed binning + memmap training →
streamed CSR factorization → outlier scores → one imputation iteration →
tiered serving burst) at 1M×20 rows by default, with every scratch file
under one temp dir (cleaned on success AND failure) and peak traced memory
asserted against ``--memory-ceiling-mb`` when ``--assert-memory-ceiling``
is set.  Results land in ``BENCH_scaling.json``.
"""
from __future__ import annotations

import argparse
import json
import resource
import tempfile
import time
import tracemalloc
from typing import Dict, List

import numpy as np

from repro.core.compile_cache import configure_compile_cache
from repro.core.api import ForestKernel
from repro.core.leafmap import sparse_bytes
from repro.data.synthetic import gaussian_classes

__all__ = ["measure_kernel_cost", "scaling_curve", "fit_slope", "run",
           "run_out_of_core"]


def measure_kernel_cost(fk: ForestKernel) -> Dict[str, float]:
    t0 = time.perf_counter()
    fk.build_kernel_cache()
    t1 = time.perf_counter()
    P = fk.kernel(set_diagonal=False)
    t2 = time.perf_counter()
    mem = fk.memory_bytes()
    mem_total = mem["total"] + sparse_bytes(P)
    return {"cache_s": t1 - t0, "kernel_s": t2 - t1,
            "total_s": t2 - t0, "bytes": float(mem_total),
            "nnz": float(P.nnz), "lambda_bar": float(P.nnz) / P.shape[0]}


def scaling_curve(ns, *, method="gap", model_type="rf", n_trees=30,
                  min_samples_leaf=1, max_depth=64, d=30, n_classes=7,
                  seed=0, reps=1) -> List[Dict]:
    rows = []
    for n in ns:
        X, y = gaussian_classes(n, d=d, n_classes=n_classes, seed=seed)
        fk = ForestKernel(model_type=model_type, kernel_method=method,
                          n_trees=n_trees, min_samples_leaf=min_samples_leaf,
                          max_depth=max_depth, seed=seed)
        fk.fit_forest(X, y)
        best = None
        for _ in range(reps):
            fk.Q_ = fk.W_ = None
            m = measure_kernel_cost(fk)
            best = m if best is None else min(best, m, key=lambda r: r["total_s"])
        best.update({"n": n, "method": method, "model": model_type,
                     "n_min": min_samples_leaf, "depth": max_depth})
        rows.append(best)
    return rows


def fit_slope(rows, xkey="n", ykey="total_s") -> float:
    x = np.log([r[xkey] for r in rows])
    y = np.log([max(r[ykey], 1e-9) for r in rows])
    return float(np.polyfit(x, y, 1)[0])


def run(fast: bool = True, out=print):
    ns = [2000, 4000, 8000, 16000, 32000] if fast else \
        [4000, 8000, 16000, 32000, 64000, 128000]
    out("table,variant,n,time_s,bytes,nnz,lambda_bar")

    slopes = {}
    # (ii) across proximity definitions (paper Fig 4.2 middle)
    for method in ["original", "kerf", "oob", "gap"]:
        rows = scaling_curve(ns, method=method)
        for r in rows:
            out(f"fig4.2-method,{method},{r['n']},{r['total_s']:.4f},"
                f"{r['bytes']:.0f},{r['nnz']:.0f},{r['lambda_bar']:.1f}")
        slopes[f"time[{method}]"] = fit_slope(rows)
        slopes[f"mem[{method}]"] = fit_slope(rows, ykey="bytes")

    # forest type ablation (Fig H.1 row 2)
    rows = scaling_curve(ns, method="kerf", model_type="et")
    for r in rows:
        out(f"figH.1-et,kerf,{r['n']},{r['total_s']:.4f},{r['bytes']:.0f},"
            f"{r['nnz']:.0f},{r['lambda_bar']:.1f}")
    slopes["time[et]"] = fit_slope(rows)

    # min leaf size ablation (Fig 4.2 bottom)
    for n_min in [1, 5, 20]:
        rows = scaling_curve(ns[:4] if fast else ns, method="gap",
                             min_samples_leaf=n_min)
        for r in rows:
            out(f"fig4.2-nmin,{n_min},{r['n']},{r['total_s']:.4f},"
                f"{r['bytes']:.0f},{r['nnz']:.0f},{r['lambda_bar']:.1f}")
        slopes[f"time[nmin={n_min}]"] = fit_slope(rows)

    # depth truncation (Fig H.1 bottom: approaches quadratic)
    for depth in [64, 8]:
        rows = scaling_curve(ns[:4] if fast else ns, method="original",
                             max_depth=depth)
        for r in rows:
            out(f"figH.1-depth,{depth},{r['n']},{r['total_s']:.4f},"
                f"{r['bytes']:.0f},{r['nnz']:.0f},{r['lambda_bar']:.1f}")
        slopes[f"time[depth={depth}]"] = fit_slope(rows)
        slopes[f"mem[depth={depth}]"] = fit_slope(rows, ykey="bytes")

    for k, v in slopes.items():
        out(f"slope,{k},,{v:.3f},,,")
    return slopes


# ---------------------------------------------------------------------------
# out-of-core end-to-end mode
# ---------------------------------------------------------------------------

def _gen_memmap_dataset(path, n: int, d: int, n_classes: int, seed: int,
                        sep: float):
    """Chunk-generate the dataset straight into a float64 memmap so the
    bench itself never holds the full X in RAM (the point of the mode).

    ``sep`` keeps the classes overlapping (default 0.8): cleanly separable
    mixtures go pure early, trees stop splitting, and leaf occupancy — and
    with it proximity row density λ̄ — grows linearly with n instead of
    staying bounded (the regime the paper's scaling claim lives in).
    """
    X = np.memmap(path, dtype=np.float64, mode="w+", shape=(n, d))
    y = np.empty(n, dtype=np.int64)
    chunk = max(1, (64 << 20) // (8 * d))
    for ci, i0 in enumerate(range(0, n, chunk)):
        i1 = min(i0 + chunk, n)
        Xc, yc = gaussian_classes(i1 - i0, d=d, n_classes=n_classes,
                                  sep=sep, seed=seed + ci)
        X[i0:i1] = Xc
        y[i0:i1] = yc
    X.flush()
    return X, y


def _inject_fit_failure() -> None:
    """--inject-failure: make the batched trainer raise mid-fit, so CI can
    check the scratch dir is cleaned on the *failure* path too."""
    import repro.forest.ensemble as _ens
    import repro.forest.training as _tr

    def _boom(*a, **k):
        raise RuntimeError("injected failure (bench --inject-failure)")

    _tr.fit_forest_binned = _boom
    _ens.fit_forest_binned = _boom
    _tr.fit_tree_binned = _boom
    _ens.fit_tree_binned = _boom


def run_out_of_core(args, out=print) -> Dict:
    budget = args.memory_budget_mb << 20
    if args.inject_failure:
        _inject_fit_failure()
    tracemalloc.start()
    stages: Dict[str, float] = {}
    stage_peaks: Dict[str, float] = {}
    t_start = time.perf_counter()
    rng = np.random.default_rng(args.seed)

    def _mark(name: str, t0: float) -> None:
        # per-stage traced high-water: reset after each stage so the JSON
        # attributes the overall peak to the stage that caused it
        stages[name] = time.perf_counter() - t0
        stage_peaks[name] = tracemalloc.get_traced_memory()[1] / (1 << 20)
        tracemalloc.reset_peak()

    with tempfile.TemporaryDirectory(prefix="oocscale_",
                                     dir=args.scratch_root) as scratch:
        out(f"# scratch: {scratch}")
        X, y = _gen_memmap_dataset(f"{scratch}/X.mm", args.n, args.d,
                                   args.classes, args.seed, args.sep)
        fk = ForestKernel(
            kernel_method=args.method, n_trees=args.trees,
            max_depth=args.max_depth, min_samples_leaf=args.min_samples_leaf,
            seed=args.seed, tree_backend=args.tree_backend,
            scratch_dir=scratch, memory_budget_bytes=budget)

        t0 = time.perf_counter()
        fk.fit_forest(X, y)                     # streamed bin -> memmap train
        _mark("fit_s", t0)
        out(f"# fit: {stages['fit_s']:.1f}s")

        t0 = time.perf_counter()
        fk.build_kernel_cache()                 # chunked route + streamed CSR
        _mark("factorize_s", t0)
        engine_mem = fk.engine.memory_bytes()
        out(f"# factorize: {stages['factorize_s']:.1f}s, engine "
            f"{engine_mem['total'] / 1e6:.0f}MB")

        t0 = time.perf_counter()
        scores = fk.outlier_scores()
        _mark("outliers_s", t0)
        out(f"# outliers: {stages['outliers_s']:.1f}s "
            f"(max score {float(np.max(scores)):.2f})")

        # one imputation iteration on a NaN-injected copy (bounded width
        # keeps the copy the only full-X-sized RAM array in the bench)
        t0 = time.perf_counter()
        Xnan = np.asarray(X).copy()
        n_miss = max(1, int(args.n * args.d * args.missing_frac))
        mi = rng.integers(0, args.n, n_miss)
        mj = rng.integers(0, args.d, n_miss)
        Xnan[mi, mj] = np.nan
        imp = fk.impute(Xnan, y, n_iter=1)
        assert not np.isnan(imp.X_imputed_).any()
        del Xnan, imp
        _mark("impute_s", t0)
        out(f"# impute(1 iter): {stages['impute_s']:.1f}s")

        # tiered serving burst (shallow -> compressed -> full ladder)
        t0 = time.perf_counter()
        srv = fk.serve_tiered(prefix_depth=args.prefix_depth,
                              n_prototypes=args.prototypes,
                              proto_k=args.proto_k, n_slots=args.batch_rows)
        pool = [np.asarray(X[rng.integers(0, args.n, args.batch_rows)])
                for _ in range(4)]
        kinds = ["predict", "predict", "topk", "outlier"]
        srv.start()
        try:
            uids = [srv.submit(kinds[i % len(kinds)], pool[i % len(pool)],
                               k=10) for i in range(args.requests)]
            srv.wait(uids, timeout=600.0)
        finally:
            srv.stop()
        done = sum(r.result is not None for r in srv.finished)
        _mark("serving_s", t0)
        out(f"# serving burst: {stages['serving_s']:.1f}s "
            f"({done}/{args.requests} completed)")

    total_s = time.perf_counter() - t_start
    traced_peak = max(stage_peaks.values()) * (1 << 20)
    tracemalloc.stop()
    ru_maxrss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    row = {
        "mode": "out_of_core",
        "n": args.n, "d": args.d, "n_trees": args.trees, "sep": args.sep,
        "method": args.method, "max_depth": args.max_depth,
        "min_samples_leaf": args.min_samples_leaf,
        "memory_budget_mb": args.memory_budget_mb,
        "memory_ceiling_mb": args.memory_ceiling_mb,
        "stages_s": {k: round(v, 3) for k, v in stages.items()},
        "total_s": round(total_s, 3),
        "peak_traced_mb": round(traced_peak / (1 << 20), 1),
        "stage_peak_traced_mb": {k: round(v, 1)
                                 for k, v in stage_peaks.items()},
        # lifetime high-water RSS of the whole process (info only: includes
        # interpreter + page-cache-touched memmaps, not just numpy allocs)
        "ru_maxrss_mb": round(ru_maxrss_mb, 1),
        "engine_memory_bytes": engine_mem,
        "serving": {"requests": args.requests, "completed": int(done)},
    }
    row["within_ceiling"] = bool(row["peak_traced_mb"]
                                 <= args.memory_ceiling_mb)
    out(json.dumps(row, indent=2))

    if args.out:
        try:
            existing = json.load(open(args.out))
        except (OSError, ValueError):
            existing = {}
        existing["out_of_core"] = row
        with open(args.out, "w") as f:
            json.dump(existing, f, indent=2)
        out(f"# wrote {args.out}")

    if args.assert_memory_ceiling and not row["within_ceiling"]:
        raise SystemExit(
            f"peak traced memory {row['peak_traced_mb']:.0f}MB exceeds the "
            f"configured ceiling {args.memory_ceiling_mb}MB")
    if done != args.requests:
        raise SystemExit(
            f"serving burst incomplete: {done}/{args.requests}")
    return row


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out-of-core", action="store_true",
                   help="run the disk-resident end-to-end pipeline instead "
                        "of the scaling curves")
    p.add_argument("--full", action="store_true",
                   help="curves mode: larger n grid")
    p.add_argument("--n", type=int, default=1_000_000)
    p.add_argument("--d", type=int, default=20)
    p.add_argument("--classes", type=int, default=5)
    p.add_argument("--sep", type=float, default=0.8,
                   help="class separation; keep low so leaf occupancy (and "
                        "proximity row density) stays bounded as n grows")
    p.add_argument("--trees", type=int, default=15)
    p.add_argument("--max-depth", type=int, default=32)
    p.add_argument("--min-samples-leaf", type=int, default=3)
    p.add_argument("--method", default="gap")
    p.add_argument("--tree-backend", default="auto")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--memory-budget-mb", type=int, default=512,
                   help="engine/trainer transient budget (memory_budget_bytes)")
    p.add_argument("--memory-ceiling-mb", type=int, default=4096,
                   help="asserted ceiling on tracemalloc peak")
    p.add_argument("--assert-memory-ceiling", action="store_true")
    p.add_argument("--missing-frac", type=float, default=0.002)
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--batch-rows", type=int, default=64)
    p.add_argument("--prefix-depth", type=int, default=4)
    p.add_argument("--prototypes", type=int, default=3)
    p.add_argument("--proto-k", type=int, default=10)
    p.add_argument("--scratch-root", default=None,
                   help="parent dir for the run's temp scratch dir")
    p.add_argument("--inject-failure", action="store_true",
                   help="raise mid-fit (CI scratch-hygiene check)")
    p.add_argument("--out", default=None, help="JSON output path")
    return p.parse_args(argv)


def main(argv=None) -> None:
    args = _parse_args(argv)
    configure_compile_cache()
    if args.out_of_core:
        run_out_of_core(args)
        return
    slopes = run(fast=not args.full)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"slopes": slopes}, f, indent=2)


if __name__ == "__main__":
    main()
