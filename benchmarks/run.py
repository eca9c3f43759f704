"""Benchmark orchestrator — one section per paper table/figure.

  PYTHONPATH=src:. python -m benchmarks.run [--full] [--skip roofline]

Prints CSV blocks per section (tee'd to bench_output.txt by the runner).
"""
from __future__ import annotations

import argparse
import time

from repro.core.compile_cache import configure_compile_cache


def _section(name):
    print(f"\n===== {name} =====", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow; default is CI-scale)")
    ap.add_argument("--skip", nargs="*", default=[])
    args = ap.parse_args()
    configure_compile_cache()
    fast = not args.full

    t0 = time.time()

    if "oob" not in args.skip:
        _section("Fig 4.1 — OOB separability ratio (Prop G.1)")
        from benchmarks.bench_oob_ratio import run as run_oob
        run_oob(fast=fast)

    if "scaling" not in args.skip:
        _section("Fig 4.2 / H.1 — time & memory scaling of exact kernels")
        from benchmarks.bench_scaling import run as run_scaling
        run_scaling(fast=fast)

    if "prediction" not in args.skip:
        _section("Table I.1 — kernel-weighted prediction accuracy")
        from benchmarks.bench_prediction import run as run_pred
        run_pred(fast=fast)

    if "leafpca" not in args.skip:
        _section("Fig 4.3 — manifold learning on leaf coordinates")
        from benchmarks.bench_leafpca import run as run_pca
        run_pca(fast=fast)

    if "kernels" not in args.skip:
        _section("Pallas kernel micro-benchmarks (interpret-mode shapes)")
        from benchmarks.bench_kernels import run as run_kern
        run_kern(fast=fast)

    if "roofline" not in args.skip:
        _section("§Roofline — per (arch x shape) from dry-run records")
        from benchmarks.roofline import report
        try:
            rows = report()
            if not rows:
                print("(no dry-run records found — run "
                      "`python -m repro.launch.dryrun --all --both-meshes` first)")
        except Exception as e:  # records may be in-flight
            print(f"roofline report unavailable: {e}")

    print(f"\n[benchmarks] total {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
