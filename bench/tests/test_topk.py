"""The top-k cells on the CPU at a tiny size: the boosted reference weights,
the top-k op's dense-row reference, whole runs of both cells through the
harness, and the controls its limit is set between."""
import json

import numpy as np
import pytest

from bench import harness, lookup
from bench.reference import ReferenceForest

CELLS = ["higgs.oos-topk", "higgs-gbt.oos-topk"]


def _tiny_gbt(jax_cpu, n_trees=6):
    cfg = json.loads((harness.BENCH / "configs/higgs-gbt100.json")
                     .read_text())
    cfg["n_train"], cfg["forest"]["n_trees"] = 800, n_trees
    return harness.fit(cfg)


def test_boosted_weights_match_the_program(jax_cpu):
    """``bench/weights/boosted.py`` against the program's ``Boosted``
    assignment, on a tiny boosted forest: q = w = sqrt(v_t / sum v)."""
    s = _tiny_gbt(jax_cpu)
    ref = harness.reference(s)
    weights = lookup.load("weights", "boosted")
    prog = s.kernel.assignment.query_weights(s.kernel.ctx.leaves)
    for t in range(ref.T):
        leaf = ref.model.train_leaf[t]
        np.testing.assert_allclose(
            weights.reference_weights(ref.model, t, leaf), prog[:, t],
            rtol=1e-12)
        np.testing.assert_array_equal(
            weights.query_weights(ref.model, t, leaf),
            weights.reference_weights(ref.model, t, leaf))
    assert np.ptp(prog[0]) > 0          # the stages weigh differently


@pytest.mark.parametrize("method", ["original", "boosted"])
def test_reference_rows_against_brute_force(jax_cpu, method):
    """The op's sparse-product proximity rows equal the brute-force dense
    P(x, j) = sum_t q_t(x) w_t(j) [leaf_t(x) == leaf_t(j)], in blocks."""
    from bench.ops.topk import proximity_rows
    if method == "boosted":
        s = _tiny_gbt(jax_cpu)
    else:
        cfg = json.loads((harness.BENCH / "configs/higgs-rf100.json")
                         .read_text())
        cfg["n_train"], cfg["forest"]["n_trees"] = 600, 5
        s = harness.fit(cfg)
    ref = harness.reference(s)
    X = s.X_train[:150] + 0.01
    qn = ref.query_nodes(X)
    want = np.zeros((len(X), len(s.X_train)))
    for t in range(ref.T):
        q = ref.weights.query_weights(ref.model, t, qn[t])
        same = qn[t][:, None] == ref.model.train_leaf[t][None, :]
        want += same * (q[:, None] * ref.w[t][None, :])
    got = np.concatenate([P for _, P in proximity_rows(ref, X, block=64)])
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=0.0)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_topk_cell_is_correct(cpu_run, workload, traced):
    res = cpu_run(workload, traced=traced)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    spec = harness.load_spec()
    kind = "per_layer" if traced else "end_to_end"
    want = {m["name"] for m in harness.metrics_for(spec, workload, kind)}
    if traced:    # the CPU has no device plane: device readers may be silent
        assert set(res["metrics"]) <= want
        assert want >= {"topk_device_ms", "topk_roofline"}
    else:
        assert set(res["metrics"]) == want == {"rows_per_s", "setup_s"}
    assert res["checks"]["topk_gap"]["value"] <= \
        res["checks"]["topk_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_bfloat16_control_fails_the_limit(jax_cpu, tiny, workload):
    """The control computed in bfloat16 fails ``topk_gap``'s limit on the
    original-weights forest and on the boosted one; float32 passes."""
    from bench import control
    r = control.readings(workload, 43, 0.3,
                         devices=lambda c: jax_cpu.devices()[:c], sizes=tiny)
    limit = json.load(open(harness.BENCH / "traffic/oos-topk.json")
                      )["limits"]["topk_gap"]
    assert r["float64"]["topk_gap"] <= limit
    assert r["float32"]["topk_gap"] <= limit
    assert r["bfloat16"]["topk_gap"] > 3 * limit


def test_wrong_answers_read_inf(jax_cpu):
    """A repeated or out-of-range index, a non-finite value or a wrong
    shape is no answer; a value off by a part in 1e4 fails the limit."""
    from bench.ops.topk import Op
    s = _tiny_gbt(jax_cpu, n_trees=4)
    op = Op(s, {"k": 3})
    ref = ReferenceForest(s.kernel.forest.trees_, s.kernel.forest.inbag_,
                          s.X_train, s.y_train, "boosted",
                          tree_weights=s.kernel.forest.tree_weights_)
    X = s.X_train[:20] + 0.01
    ix, v = op(X)
    assert op.gaps(ref, X, (ix, v))["topk_gap"] <= 1e-12
    bad = [(ix[:, :2], v[:, :2]), (np.where(ix == ix[0, 0], ix[0, 1], ix), v),
           (ix + len(s.X_train), v), (ix, np.where(v == v[0, 0], np.nan, v))]
    for b in bad:
        assert op.gaps(ref, X, b)["topk_gap"] == float("inf")
    off = v.copy()
    off[3, 1] *= 1.0001
    assert op.gaps(ref, X, (ix, off))["topk_gap"] > 1e-5
