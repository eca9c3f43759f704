"""Whole runs of the harness with the CPU standing in for the chip, the
control, the faults that ``correct`` has to catch, and the lookup of a
cell's files by name."""
import json
import shutil

import numpy as np
import pytest

from bench import harness

CELLS = ["covertype.oos-predict", "higgs.oos-predict"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_run_is_correct(cpu_run, workload, traced):
    res = cpu_run(workload, traced=traced)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    spec = harness.load_spec()
    kind = "per_layer" if traced else "end_to_end"
    want = {m["name"] for m in harness.metrics_for(spec, workload, kind)}
    if traced:    # the CPU has no device plane: device readers may be silent
        assert "route_ms.batch" in res["metrics"]
        assert set(res["metrics"]) <= want
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == want
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_set_up_is_the_same_every_run(jax_cpu):
    """The configuration fixes the forest: every run fits the same one."""
    cfg = json.loads((harness.BENCH / "configs/higgs-rf100.json").read_text())
    cfg["n_train"], cfg["forest"]["n_trees"] = 500, 5
    a, b = (harness.fit(cfg).kernel.engine for _ in range(2))
    assert np.array_equal(a.gl, b.gl) and np.array_equal(a.q, b.q)


def test_no_chip_no_result(jax_cpu, capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    rc = harness.main(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "TPU" in out.err


# ----------------------------------------------------- control, faults --
@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_float32_passes(jax_cpu, tiny, workload):
    from bench import control
    r = control.readings(workload, 41, 0.3,
                         devices=lambda c: jax_cpu.devices()[:c], sizes=tiny)
    limit = json.load(open(harness.BENCH / "traffic/oos-predict.json")
                      )["limits"]["predict_gap"]
    assert r["float64"]["predict_gap"] <= limit
    assert r["float32"]["predict_gap"] <= limit
    assert r["bfloat16"]["predict_gap"] > 3 * limit


def _broken_fit(monkeypatch, breaker):
    real = harness.fit

    def fit(cfg):
        s = real(cfg)
        breaker(s.kernel.engine)
        return s
    monkeypatch.setattr(harness, "fit", fit)


def test_fault_answer_altered(cpu_run, monkeypatch):
    """One row's scores changed where the engine produces them."""
    def breaker(engine):
        real = engine.predict

        def predict(*a, **kw):
            out = real(*a, **kw).copy()
            out[len(out) // 3] *= 1.001
            return out
        engine.predict = predict
    _broken_fit(monkeypatch, breaker)
    big = {"traffic": {"batch_rows": 32, "pool_batches": 6,
                       "check_rows": 10 ** 6},
           "cfg": {"n_train": 1500}, "forest": {"n_trees": 10}}
    assert not cpu_run(CELLS[0], sizes=big)["correct"]


def test_fault_half_the_trees(cpu_run, monkeypatch):
    """Half of the trees left out, the mean taken over the rest."""
    def breaker(engine):
        real = engine.assignment.oos_query_weights

        def weights(leaves):
            q = real(leaves).copy()
            half = q.shape[1] // 2
            q[:, :half] *= q.shape[1] / half
            q[:, half:] = 0.0
            return q
        engine.assignment.oos_query_weights = weights
    _broken_fit(monkeypatch, breaker)
    assert not cpu_run(CELLS[1])["correct"]


# ------------------------------------------------------ found by name --
OP = """
import numpy as np


class Op:
    name = "mass"
    numbers = ("mass_gap",)

    def __init__(self, s, traffic):
        self.engine, self.y = s.kernel.engine, s.kernel.ctx.y
        self.C = s.cfg["n_classes"]

    def __call__(self, X):
        return self.engine.predict(self.y, n_classes=self.C, X=X).sum(axis=1)

    def sample(self, answers, idx):
        return np.concatenate(answers)[idx]

    def gaps(self, ref, X, got, precision="float64"):
        want = ref.predict(X, self.C).sum(axis=1)
        return {"mass_gap": float(np.abs(got - want).max())}

    def work_bytes(self, n_rows):
        return 8 * n_rows
"""

LOOP = """
import time

from bench import data
from bench.harness import Call, Window


def prepare(op, engine, cfg, traffic, seed):
    return data.query_rows(cfg, seed, traffic["calls"] * traffic["rows"]
                           ).reshape(traffic["calls"], traffic["rows"], -1)


def run(op, engine, pool, seconds, traced):
    start, calls, answers = time.perf_counter(), [], []
    for X in pool:
        t0 = time.perf_counter()
        answers.append(op(X))
        calls.append(Call(op.name, X, t0, t0, time.perf_counter()))
    return Window(calls, answers, time.perf_counter() - start)


def end_to_end(w):
    return {"calls_per_s": len(w.calls) / w.seconds}
"""


@pytest.mark.parametrize("traced", [False, True])
def test_cell_added_as_files_alone(jax_cpu, tmp_path, monkeypatch, traced):
    """A configuration, a traffic mix with a new op and a new loop, a cell
    and metrics added as files and entries of BENCHMARK.json run without
    editing any other file."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench")
    spec = harness.load_spec()
    cfg = json.loads((root / "bench/configs/higgs-rf100.json").read_text())
    cfg.update(name="higgs-wide", n_train=1200)
    (root / "bench/configs/higgs-wide.json").write_text(json.dumps(cfg))
    (root / "bench/ops/mass.py").write_text(OP)
    (root / "bench/loops/fixed.py").write_text(LOOP)
    (root / "bench/traffic/fixed-mass.json").write_text(json.dumps(
        {"loop": "fixed", "op": "mass", "calls": 3, "rows": 16,
         "check_rows": 48, "limits": {"mass_gap": 1e-9}}))
    (root / "bench/metrics/rows_per_call.py").write_text(
        "def read(run):\n    return sum(len(c.X) for c in run.calls) / "
        "len(run.calls)\n")
    spec["configs"].append({"name": "higgs-wide", "source": "test",
                            "file": "bench/configs/higgs-wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "higgs.fixed-mass", "config":
                              "higgs-wide", "traffic": "fixed-mass",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["higgs.fixed-mass"]})
    spec["per_layer"].append({"name": "rows_per_call", "unit": "rows",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "calls_per_s",
                              "workloads": ["higgs.fixed-mass"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    res = harness.run("higgs.fixed-mass", 5, 0.3, traced, 0.0,
                      devices=lambda c: jax_cpu.devices()[:c],
                      sizes={"forest": {"n_trees": 8}})
    assert res["correct"] and res["attempted"] == 3
    assert res["checks"]["mass_gap"]["value"] <= 1e-9
    if traced:
        assert res["metrics"]["rows_per_call"]["value"] == 16
    else:
        assert set(res["metrics"]) == {"calls_per_s", "setup_s"}
