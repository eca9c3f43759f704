"""The benchmark's own arithmetic: trace reduction, work counts, peaks, the
plain reference and the data generators."""
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import data, trace, work
from bench.reference import ReferenceForest

DATA = Path(__file__).resolve().parent / "data"


# ------------------------------------------------------------- trace --
def _synthetic():
    ns = 1e9    # seconds -> ns
    return {"device": [["/device:TPU:0", "%while.1", 0 * ns, 10 * ns],
                       ["/device:TPU:0", "%fusion.2", 2 * ns, 5 * ns],
                       ["/device:TPU:0", "%fusion.3", 20 * ns, 30 * ns],
                       ["/device:TPU:0", "%fusion.3", 50 * ns, 60 * ns]],
            "host": [["bench:window", 0, 45 * ns],
                     ["bench:route", 10 * ns, 20 * ns],
                     ["bench:predict", 20 * ns, 35 * ns]]}


def test_reduction_by_hand():
    r = trace.Reduction(_synthetic())
    assert r.window_s == 45.0
    assert r.busy_s == 20.0                       # the op at 50-60 is outside
    assert r.idle_share == pytest.approx(25 / 45)
    np.testing.assert_allclose(r.span_times("predict"), [(15.0, 10.0)])
    np.testing.assert_allclose(r.span_times("route"), [(10.0, 0.0)])
    ops = r.top_ops()
    assert [k for k, _ in ops] == ["%fusion.3", "%while.1", "%fusion.2"]
    np.testing.assert_allclose([v for _, v in ops], [10.0, 7.0, 3.0])
    gaps = r.idle_gaps()
    assert [k for k, _ in gaps] == ["other", "route"]
    np.testing.assert_allclose([v for _, v in gaps], [15.0, 10.0])


def test_reduction_of_recorded_chip_trace():
    """Two predict calls of covertype.oos-predict traced on a TPU v5e."""
    ev = trace.load_events(DATA / "trace_predict_2calls.json")
    r = trace.Reduction(ev)
    # busy union, counted independently on a microsecond grid
    t0 = r.t0
    grid = np.zeros(int((r.t1 - t0) / 1e3) + 1, dtype=bool)
    for _, _, a, b in ev["device"]:
        a, b = max(a, r.t0), min(b, r.t1)
        if b > a:
            grid[int((a - t0) / 1e3):int((b - t0) / 1e3)] = True
    assert r.busy_s == pytest.approx(grid.sum() * 1e-6, rel=1e-3)
    assert 0.0 < r.idle_share < 1.0
    # the device works through each predict call and idles while routing
    for wall, dev in r.span_times("predict"):
        assert 0.8 * wall < dev <= wall
    for wall, dev in r.span_times("route"):
        assert dev < 0.1 * wall
    # self times add up to the busy time; gaps to the idle time
    assert sum(v for _, v in r.top_ops(10 ** 6)) == pytest.approx(r.busy_s)
    gaps = r.idle_gaps(10 ** 6)
    assert sum(v for _, v in gaps) == pytest.approx(r.window_s - r.busy_s)
    assert gaps[0][0] in ("route", "predict")


def test_span_readers_use_device_time():
    """predict_roofline: least chip time over the device-busy time in the
    ``predict`` spans, not over the calls' wall time; silent untraced."""
    from bench import harness
    roofline = harness.load("metrics", "predict_roofline").read
    device_ms = harness.load("metrics", "predict_device_ms").read
    call = harness.Call("predict", np.zeros((1, 1)), 0.0, 0.0, 100.0,
                        bytes=int(819e9))         # 1 s of HBM traffic
    untraced = harness.RunData([call], None, "TPU v5 lite")
    assert roofline(untraced) is None and device_ms(untraced) is None
    traced = harness.RunData([call], trace.Reduction(_synthetic()),
                             "TPU v5 lite")
    assert device_ms(traced) == pytest.approx(10e3)
    assert roofline(traced) == pytest.approx(10.0)


def test_reduction_needs_window():
    ev = _synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError, match="window"):
        trace.Reduction(ev)


# -------------------------------------------------------- work, peaks --
def _tiny_forest():
    """Two stumps over rows (0,0) (0,1) (1,0) (1,1): tree 0 splits x0 at
    0.5, tree 1 splits x1 at 0.5; nodes 1 and 2 are the leaves."""
    stump = lambda f: SimpleNamespace(  # noqa: E731
        feature=np.array([f, -1, -1]), threshold=np.float32([0.5, 0, 0]),
        left=np.array([1, -1, -1]), right=np.array([2, -1, -1]))
    X = np.array([[0., 0.], [0., 1.], [1., 0.], [1., 1.]])
    y = np.array([0, 1, 0, 1])
    inbag = np.array([[1, 2, 0, 1], [0, 1, 1, 2]])
    return [stump(0), stump(1)], inbag, X, y


def test_work_counts_by_hand():
    trees, inbag, X, y = _tiny_forest()
    # per (row, tree): leaf id, weight, C class sums; then the answer
    assert work.predict_bytes(1, n_trees=2, n_classes=2) == \
        2 * (4 + 4 + 8) + 8
    assert work.predict_bytes(3, n_trees=1, n_classes=7) == \
        3 * (4 + 4 + 28) + 3 * 28


def test_peaks_table():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops"] == 197e12
    assert "TPU v5e" in p["source"]
    assert work.least_seconds("TPU v5 lite", n_bytes=819e9) == 1.0
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


# --------------------------------------------------------- reference --
def test_reference_by_hand():
    trees, inbag, X, y = _tiny_forest()
    q = np.array([[0., 0.]])
    gap = ReferenceForest(trees, inbag, X, y, "gap", n_threads=1)
    # tree 0: rows 0,1 with in-bag 1,2 -> w 1/3, 2/3; tree 1: rows 0,2
    # with in-bag 0,1 -> w 0, 1; q = 1/2
    np.testing.assert_allclose(gap.predict(q, 2), [[2 / 3, 1 / 3]])
    # original: q = w = 1/sqrt(2); rows 0 (both trees), 1 and 2 (one each)
    orig = ReferenceForest(trees, inbag, X, y, "original", n_threads=1)
    np.testing.assert_allclose(orig.predict(q, 2), [[1.0 + 0.5, 0.5]])


@pytest.mark.parametrize("method", ["gap", "original"])
def test_reference_agrees_with_scipy_engine(jax_cpu, method):
    from repro.core.api import ForestKernel
    cfg = json.loads((DATA.parents[1] / "configs" /
                      "covertype-rf100.json").read_text())
    cfg["n_train"] = 800
    X, y = data.training_rows(cfg)
    Xq = data.query_rows(cfg, 3, 50)
    fk = ForestKernel(kernel_method=method, n_trees=12, seed=3,
                      tree_backend="native", engine_backend="scipy").fit(X, y)
    ref = ReferenceForest(fk.forest.trees_, fk.forest.inbag_, X, y, method)
    np.testing.assert_allclose(ref.predict(Xq, 7),
                               fk.engine.predict(y, n_classes=7, X=Xq),
                               rtol=0, atol=1e-12)


def test_lower_precision_reference_departs():
    trees, inbag, X, y = _tiny_forest()
    ref = ReferenceForest(trees, inbag, X, y, "gap", n_threads=1)
    q = np.array([[0., 0.]])
    exact = ref.predict(q, 2)
    assert np.abs(ref.predict(q, 2, "bfloat16") - exact).max() > 1e-4
    assert np.abs(ref.predict(q, 2, "float32") - exact).max() < 1e-6


# --------------------------------------------------------- generators --
def _cfg(name):
    return json.loads((DATA.parents[1] / "configs" / f"{name}.json"
                       ).read_text())


@pytest.mark.parametrize("name", ["covertype-rf100", "higgs-rf100"])
def test_generators_deterministic(name):
    """Training rows by the configuration's model seed, queries by --seed."""
    cfg = dict(_cfg(name), n_train=2000)
    a, ya = data.training_rows(cfg)
    b, yb = data.training_rows(cfg)
    c, _ = data.training_rows(dict(cfg, model_seed=1))
    assert np.array_equal(a, b) and np.array_equal(ya, yb)
    assert not np.array_equal(a, c)
    assert a.shape == (2000, cfg["n_features"])
    q = data.query_rows(cfg, 2 ** 33 + 1, 64)
    assert np.array_equal(q, data.query_rows(cfg, 2 ** 33 + 1, 64))
    assert not np.array_equal(q, data.query_rows(cfg, 2 ** 33 + 2, 64))
    assert not np.array_equal(q[:10], a[:10])


def test_covertype_layout_and_priors():
    cfg = dict(_cfg("covertype-rf100"), n_train=60000)
    X, y = data.training_rows(cfg)
    assert X.shape[1] == 54
    assert np.array_equal(X[:, :10], np.rint(X[:, :10]))
    assert (X[:, 10:14].sum(axis=1) == 1).all()       # one wilderness area
    assert (X[:, 14:].sum(axis=1) == 1).all()         # one soil type
    p = np.asarray(cfg["class_counts"]) / sum(cfg["class_counts"])
    np.testing.assert_allclose(np.bincount(y, minlength=7) / len(y), p,
                               atol=0.01)


def test_higgs_layout():
    cfg = dict(_cfg("higgs-rf100"), n_train=20000)
    X, y = data.training_rows(cfg)
    assert X.shape[1] == 28
    assert set(np.unique(X[:, 8::4][:, :4])) <= {0.0, 1.0865, 2.173}
    assert (X[:, 21:] > 0).all() and (X[:, [0, 5, 9, 13, 17]] > 0).all()
    assert abs(y.mean() - 0.53) < 0.02


# ------------------------------------------------------- bit identity --
# sha1 of the rows, the forest's leaf count and the reference's answers of
# both configurations, as the harness first computed them with its
# generators and weight assignments in data.py and reference.py: moving
# them into files found by name moves no reading of the benchmark.
SUMS = {
    "covertype-rf100": {
        "train": "46c74cf42d983b7849c33c981e19624b72b7dc08",
        "query": "3022cefe91d8636f58169c46cfaf65f3c8f6b1b6",
        "leaves": 2587,
        "original.float64": "6de3121c071d90f3a65248286037128f8b6b93e2",
        "original.bfloat16": "da1a54b116989f2b87058b4560c17626f8cc92fb",
        "gap.float64": "b334e00d85623a25102c7f382a485595df9c56d7",
        "gap.bfloat16": "45e2b80c7b006bd1056ea21d7c3931c8d429280c"},
    "higgs-rf100": {
        "train": "0a4d2d2e7430e973a1e7b329884b97ae18c33649",
        "query": "7914b18cf5b0e124f58c029b840a7dc9121f773d",
        "leaves": 3114,
        "original.float64": "a6418ba5c366303b6d4a526198dc2e210d2f3421",
        "original.bfloat16": "bd9647681f2846b6413a89ac773abc79940c5a39",
        "gap.float64": "7999bd44ad428c48742866c80d75e783a8e1f012",
        "gap.bfloat16": "97eaf4cd614fd973de416675a544373314c618b9"},
}
QUERY_SEED = 2 ** 31 + 7
METHODS = ["original", "gap"]


def _sha1(*arrays) -> str:
    h = hashlib.sha1()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _small(name):
    return dict(_cfg(name), n_train=2000)


@pytest.mark.parametrize("name", sorted(SUMS))
def test_rows_bit_identical(name):
    cfg = _small(name)
    assert _sha1(*data.training_rows(cfg)) == SUMS[name]["train"]
    assert _sha1(data.query_rows(cfg, QUERY_SEED, 256)) == SUMS[name]["query"]


@pytest.mark.parametrize("name", sorted(SUMS))
def test_forest_and_reference_bit_identical(jax_cpu, name):
    from bench import harness
    cfg = _small(name)
    cfg["forest"]["n_trees"] = 10
    s = harness.fit(cfg)
    assert s.kernel.ctx.total_leaves == SUMS[name]["leaves"]
    f = s.kernel.forest
    Xq = data.query_rows(cfg, QUERY_SEED, 256)
    for method in METHODS:
        ref = ReferenceForest(f.trees_, f.inbag_, s.X_train, s.y_train,
                              method, tree_weights=f.tree_weights_)
        for p in ("float64", "bfloat16"):
            got = ref.predict(Xq, cfg["n_classes"], p)
            assert _sha1(got) == SUMS[name][f"{method}.{p}"], (method, p)
