"""Benchmark tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

``cpu_run`` drives a whole run of a cell with the CPU standing in for the
chip; ``tiny`` is the size every test shrinks a cell to.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TINY = {"cfg": {"n_train": 1500}, "forest": {"n_trees": 10},
        "traffic": {"batch_rows": 32, "pool_batches": 6, "check_rows": 96}}


@pytest.fixture
def tiny():
    return {k: dict(v) for k, v in TINY.items()}


@pytest.fixture(scope="session")
def jax_cpu(tmp_path_factory):
    import jax
    from bench import harness, work
    harness.configure(tmp_path_factory.mktemp("jax_cache"))
    work.PEAKS.setdefault("cpu", work.PEAKS["TPU v5 lite"])
    return jax


@pytest.fixture
def cpu_run(jax_cpu, tiny):
    from bench import harness

    def go(workload, seed=2 ** 31 + 5, seconds=0.3, traced=False, **kw):
        return harness.run(workload, seed, seconds, traced, 0.0,
                           devices=lambda c: jax_cpu.devices()[:c],
                           sizes=kw.pop("sizes", tiny), **kw)
    return go
