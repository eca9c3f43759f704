#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, host fit, factorization, warm-up of the cell's
shapes) counts from process start.  The last line of stdout is the result
object; the compared numbers, each beside its limit, are the last lines of
stderr.  Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[0] = str(Path(__file__).resolve().parents[1])   # the checkout

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
