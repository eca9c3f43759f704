#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip at the cell's
own size: for each seed, the program's compared numbers over a short
window, and those of the reference computed in a lower precision and put
in the program's place (the control), on the same rows.

    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 3

The configurations state float32 answers, so the control is bfloat16;
float32 is read beside it, as the precision a later change may move to.
One JSON line per seed on stdout.  The benchmark's own runs never run
this.
"""
import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[1])   # the checkout

from bench import harness  # noqa: E402

PRECISIONS = ("float64", "bfloat16", "float32")


def readings(workload: str, seed: int, seconds: float, devices=None,
             sizes=None) -> dict:
    """{precision: compared numbers} over the rows one short window of the
    program answered; ``float64`` is the program itself."""
    cell, cfg, traffic = harness.load_cell(workload, harness.load_spec(),
                                           sizes)
    (devices or harness.require_devices)(cell["chips"])
    s, op, loop, prepared = harness.setup(cfg, traffic, seed)
    w = loop.run(op, s.kernel.engine, prepared, seconds, traced=False)
    ref = harness.reference(s)
    return {p: harness.check(op, ref, w, traffic["check_rows"], seed,
                             precision=p) for p in PRECISIONS}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    harness.configure()
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(args.workload, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": r,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
