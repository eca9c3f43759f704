"""Whole runs of the harness with the CPU standing in for the chip, the
control, the faults that ``correct`` has to catch, and the lookup of a
cell's files by name."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import data, harness, lookup
from bench.reference import ReferenceForest

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
    monkeypatch.setattr(lookup, "BENCH", root / "bench")
    res = harness.run("higgs.fixed-mass", 5, 0.3, traced, 0.0,
                      devices=lambda c: jax_cpu.devices()[:c],
                      sizes={"forest": {"n_trees": 8}})
    assert res["correct"] and res["attempted"] == 3
    assert res["checks"]["mass_gap"]["value"] <= 1e-9
    if traced:
        assert res["metrics"]["rows_per_call"]["value"] == 16
    else:
        assert set(res["metrics"]) == {"calls_per_s", "setup_s"}


GENERATOR = """
\"\"\"Two Gaussian classes; labels by a draw of their own.\"\"\"
import numpy as np

from bench.data import seed_rng


def world(cfg):
    rng = seed_rng(cfg["model_seed"], "world")
    return {"mean": rng.normal(0.0, 1.0, size=(2, cfg["n_features"]))}


def labels(cfg, n, rng):
    return (rng.random(n) < cfg["signal_share"]).astype(np.int64)


def rows(cfg, world, y, rng):
    return world["mean"][y] + rng.normal(size=(len(y), cfg["n_features"]))
"""

BOOSTED = """
\"\"\"Boosted proximity (Tan et al. 2020): q_t = w_t = sqrt(w_t / sum_s w_s).\"\"\"
import numpy as np


def reference_weights(model, t, leaf):
    tw = model.tree_weights
    return np.full(len(leaf), np.sqrt(tw[t] / tw.sum()))


query_weights = reference_weights
"""

GBT = {"name": "toy-gbt", "model_seed": 3, "generator": "toy",
       "n_train": 1200, "n_features": 6, "n_classes": 2, "signal_share": 0.4,
       "forest": {"model_type": "gbt", "task": "classification",
                  "kernel_method": "boosted", "n_trees": 8, "max_depth": 3,
                  "min_samples_leaf": 1, "max_features": None, "n_bins": 64,
                  "engine_backend": "pallas", "routing_backend": "auto",
                  "dtype": "float64"}}

RUN = """
import json, sys
sys.path.insert(0, sys.argv[1])
import jax
from bench import harness
harness.configure(harness.ROOT / ".bench_cache" / "jax")
res = harness.run("toy.oos-predict", 2 ** 31 + 11, 0.3, False, 0.0,
                  devices=lambda c: jax.devices()[:c],
                  sizes={"traffic": {"batch_rows": 32, "pool_batches": 4,
                                     "check_rows": 96}})
print(json.dumps(dict(res, harness=harness.__file__)))
"""


def test_deployment_added_as_files_alone(tmp_path):
    """A deployment of another kind (its own generator and labels, a
    gradient-boosted forest, boosted proximities) is new files and entries
    of BENCHMARK.json alone: its cell runs correct through the copy's own
    harness, in a process of its own, with no copied file edited."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    copied = [p.relative_to(root) for p in (root / "bench").rglob("*")
              if p.is_file()]
    (root / "bench/generators/toy.py").write_text(GENERATOR)
    (root / "bench/weights/boosted.py").write_text(BOOSTED)
    (root / "bench/configs/toy-gbt.json").write_text(json.dumps(GBT))
    spec = harness.load_spec()
    spec["configs"].append({"name": "toy-gbt", "source": "test",
                            "file": "bench/configs/toy-gbt.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy.oos-predict", "config": "toy-gbt",
                              "traffic": "oos-predict", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", RUN, str(harness.ROOT / "src")], cwd=root,
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["harness"] == str(root / "bench" / "harness.py")
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["predict_gap"]["value"] <= 1e-5
    for rel in copied:
        assert filecmp.cmp(harness.BENCH.parent / rel, root / rel,
                           shallow=False), rel


@pytest.mark.parametrize("piece", ["generator", "weights", "forest key"])
def test_missing_piece_is_named(jax_cpu, piece):
    """A configuration that names a piece the benchmark does not have fails
    before any work, naming the file looked for or the key."""
    cfg = json.loads((harness.BENCH / "configs/higgs-rf100.json").read_text())
    if piece == "generator":
        cfg["generator"] = "no-such-data"
        with pytest.raises(FileNotFoundError,
                           match=r"generators/no-such-data\.py"):
            data.training_rows(cfg)
    elif piece == "weights":
        with pytest.raises(FileNotFoundError,
                           match=r"weights/no-such-method\.py"):
            ReferenceForest([], np.zeros((0, 4)), np.zeros((4, 2)),
                            np.zeros(4), "no-such-method")
    else:
        cfg["forest"]["learning_rate"] = 0.1
        with pytest.raises(ValueError, match="learning_rate"):
            harness.fit(cfg)
