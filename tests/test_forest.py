"""Forest substrate: training, routing, bootstrap, prediction quality."""
import numpy as np
import pytest

from repro.data.synthetic import friedman1, gaussian_classes, train_test_split
from repro.forest.bootstrap import bootstrap_counts, oob_mask
from repro.forest.ensemble import ExtraTrees, GradientBoostedTrees, RandomForest
from repro.forest.trees import TreeArrays, route_forest_numpy


def test_rf_accuracy(small_cls_data):
    Xtr, ytr, Xte, yte = small_cls_data
    rf = RandomForest(n_trees=25, seed=0).fit(Xtr, ytr)
    acc = (rf.predict(Xte) == yte).mean()
    assert acc > 0.9, acc


def test_rf_oob_accuracy(small_cls_data):
    Xtr, ytr, _, _ = small_cls_data
    rf = RandomForest(n_trees=25, seed=0).fit(Xtr, ytr)
    oob_acc = (rf.oob_predict().argmax(1) == ytr).mean()
    assert oob_acc > 0.85, oob_acc


def test_extratrees_accuracy(small_cls_data):
    Xtr, ytr, Xte, yte = small_cls_data
    et = ExtraTrees(n_trees=25, seed=0).fit(Xtr, ytr)
    assert (et.predict(Xte) == yte).mean() > 0.88


def test_gbt_regression():
    X, y = friedman1(3000, seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=0)
    gb = GradientBoostedTrees(n_trees=60, task="regression", seed=0).fit(Xtr, ytr)
    r2 = 1 - ((gb.predict(Xte) - yte) ** 2).mean() / yte.var()
    assert r2 > 0.8, r2
    assert np.all(gb.tree_weights_ >= 0)
    assert abs(gb.tree_weights_.sum() - 1.0) < 1e-9


def test_gbt_binary():
    X, y = gaussian_classes(2000, d=10, n_classes=2, seed=5)
    Xtr, ytr, Xte, yte = train_test_split(X, y, seed=1)
    gb = GradientBoostedTrees(n_trees=40, task="classification", seed=0).fit(Xtr, ytr)
    assert (gb.predict(Xte) == yte).mean() > 0.9


def test_trees_grow_to_purity(small_cls_data):
    """With min_samples_leaf=1 and no depth cap, leaves should be pure."""
    Xtr, ytr, _, _ = small_cls_data
    rf = RandomForest(n_trees=3, seed=0).fit(Xtr, ytr)
    for t, tree in enumerate(rf.trees_):
        leaf_vals = tree.leaf_values()
        frac_pure = ((leaf_vals > 0).sum(1) == 1).mean()
        # Binned splits cannot always separate identical codes; near-pure is expected.
        assert frac_pure > 0.95, frac_pure


def test_routing_consistency(small_cls_data):
    """Padded TreeArrays metadata must be consistent with per-tree routing."""
    Xtr, ytr, Xte, _ = small_cls_data
    rf = RandomForest(n_trees=5, seed=0).fit(Xtr, ytr)
    leaves = route_forest_numpy(rf.trees_, Xte)
    ta = rf.tree_arrays()
    assert ta.n_trees == 5
    assert np.all(leaves < ta.n_leaves[None, :])
    assert np.all(leaves >= 0)
    assert ta.total_leaves == sum(t.n_leaves for t in rf.trees_)


def test_bootstrap_counts_shape():
    rng = np.random.default_rng(0)
    c = bootstrap_counts(500, 10, rng)
    assert c.shape == (10, 500)
    # bootstrap draws preserve total count
    assert np.all(c.sum(1) == 500)
    # OOB fraction near e^-1
    frac = oob_mask(c).mean()
    assert 0.30 < frac < 0.44, frac


def test_depth_cap_respected(small_cls_data):
    Xtr, ytr, _, _ = small_cls_data
    rf = RandomForest(n_trees=4, max_depth=4, seed=0).fit(Xtr, ytr)
    assert all(t.depth <= 5 for t in rf.trees_)
    assert all(t.n_leaves <= 16 for t in rf.trees_)


def test_min_samples_leaf(small_cls_data):
    Xtr, ytr, _, _ = small_cls_data
    rf = RandomForest(n_trees=4, min_samples_leaf=20, seed=0).fit(Xtr, ytr)
    for t in rf.trees_:
        assert t.leaf_counts().min() >= 20


# ------------------------------------------- ForestKernel's learning_rate
def _gbt_kernel(X, y, **kw):
    from repro.core.api import ForestKernel
    return ForestKernel(model_type="gbt", kernel_method="boosted", n_trees=6,
                        max_features=None, seed=0, **kw).fit_forest(X, y)


def test_forest_kernel_learning_rate_shapes_the_boosted_forest():
    """``learning_rate`` reaches the boosted trainer: max_depth 7 (the root
    counts as depth 1) grows XGBoost's depth-6 trees of at most 64 leaves,
    and the rate moves every stage's fit and weight."""
    rng = np.random.default_rng(13)
    X = rng.normal(size=(3000, 8))      # noisy labels: trees fill depth
    y = (rng.random(3000) < 1 / (1 + np.exp(-X[:, 0]))).astype(np.int64)
    fast = _gbt_kernel(X, y, learning_rate=0.3, max_depth=7)
    assert fast.forest.learning_rate == 0.3
    n_leaves = lambda fk: [int((t.feature < 0).sum())
                           for t in fk.forest.trees_]
    assert 32 < max(n_leaves(fast)) <= 64, n_leaves(fast)
    shallow = _gbt_kernel(X, y, learning_rate=0.3, max_depth=6)
    assert 16 < max(n_leaves(shallow)) <= 32, n_leaves(shallow)
    slow = _gbt_kernel(X, y, max_depth=7)
    assert slow.forest.learning_rate == 0.1
    assert not np.allclose(fast.forest.tree_weights_,
                           slow.forest.tree_weights_)


@pytest.mark.parametrize("model_type", ["rf", "gbt"])
def test_forest_kernel_without_learning_rate_is_unchanged(model_type):
    """Without the key every fit is bit-identical to the trainer's own
    defaults; on a forest that has no rate the key is refused."""
    from repro.core.api import ForestKernel
    cls = {"rf": RandomForest, "gbt": GradientBoostedTrees}[model_type]
    X, y = gaussian_classes(600, d=6, n_classes=2, seed=2)
    fk = ForestKernel(model_type=model_type, n_trees=4, max_depth=6,
                      seed=0).fit_forest(X, y)
    own = cls(n_trees=4, max_depth=6, max_features="sqrt", seed=0).fit(X, y)
    for a, b in zip(fk.forest.trees_, own.trees_):
        for f in ("feature", "threshold", "left", "right", "value"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
    if model_type == "gbt":
        assert np.array_equal(fk.forest.tree_weights_, own.tree_weights_)
    else:
        with pytest.raises(ValueError, match="learning_rate"):
            ForestKernel(model_type="rf", learning_rate=0.3,
                         n_trees=2).fit_forest(X, y)
