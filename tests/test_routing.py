"""Batched ensemble routing vs the per-tree oracle.

``route_forest_batched`` (numpy active-set walk and the JAX/Pallas kernels)
must match ``route_tree`` exactly on every (sample, tree) lane — including
heavily padded ensembles (trees of very different sizes in one TreeArrays),
single-node trees, and out-of-sample queries.
"""
import numpy as np
import pytest

from _hyp import given, settings, st
from repro.data.synthetic import gaussian_classes
from repro.forest.ensemble import RandomForest
from repro.forest.trees import (Tree, TreeArrays, route_forest_batched,
                                route_forest_numpy, route_tree)


def _single_node_tree() -> Tree:
    return Tree(feature=np.array([-1], np.int32),
                threshold=np.array([np.inf], np.float32),
                left=np.zeros(1, np.int32), right=np.zeros(1, np.int32),
                leaf_id=np.zeros(1, np.int32),
                value=np.ones((1, 2), np.float32),
                n_node_samples=np.ones(1, np.int32), depth=0)


def _random_tree(rng: np.random.Generator, n_nodes: int, d: int) -> Tree:
    """Random valid flattened tree: children ids strictly exceed the parent's.

    Nodes are laid out in id order; each internal node takes the next two
    unused ids as children, so any odd ``n_nodes`` yields a full binary tree.
    """
    assert n_nodes % 2 == 1
    feature = np.full(n_nodes, -1, np.int32)
    threshold = np.zeros(n_nodes, np.float32)
    left = np.zeros(n_nodes, np.int32)
    right = np.zeros(n_nodes, np.int32)
    next_free = 1
    depth = np.zeros(n_nodes, np.int64)
    for node in range(n_nodes):
        if next_free + 1 >= n_nodes or node >= next_free:
            continue
        if rng.random() < 0.8 or node == 0:
            feature[node] = rng.integers(0, d)
            threshold[node] = np.float32(rng.normal())
            left[node], right[node] = next_free, next_free + 1
            depth[next_free:next_free + 2] = depth[node] + 1
            next_free += 2
    leaves = feature == -1
    leaf_id = np.full(n_nodes, -1, np.int32)
    leaf_id[leaves] = np.arange(leaves.sum(), dtype=np.int32)
    n_leaves = int(leaves.sum())
    return Tree(feature=feature, threshold=threshold, left=left, right=right,
                leaf_id=leaf_id, value=np.ones((n_nodes, 2), np.float32),
                n_node_samples=np.ones(n_nodes, np.int32),
                depth=int(depth.max()))


def _assert_backends_match(trees, X):
    ta = TreeArrays.from_trees(trees)
    expected = route_forest_numpy(trees, X)
    got_np = route_forest_batched(ta, X, backend="numpy")
    np.testing.assert_array_equal(got_np, expected)
    got_jax = route_forest_batched(ta, X, backend="jax")
    np.testing.assert_array_equal(got_jax, expected)
    from repro.forest import _native
    if _native.available():
        got_c = route_forest_batched(ta, X, backend="native")
        np.testing.assert_array_equal(got_c, expected)


@settings(max_examples=12, deadline=None)
@given(n_trees=st.integers(1, 5), max_depth=st.integers(1, 7),
       n=st.integers(1, 120), seed=st.integers(0, 999))
def test_route_batched_matches_oracle_fitted(n_trees, max_depth, n, seed):
    rng = np.random.default_rng(seed)
    Xtr, ytr = gaussian_classes(200, d=5, n_classes=3, seed=seed)
    rf = RandomForest(n_trees=n_trees, max_depth=max_depth, seed=seed,
                      n_jobs=1).fit(Xtr, ytr)
    # OOS queries, float32-exact so the float32 JAX path decides identically
    X = rng.normal(size=(n, 5)).astype(np.float32).astype(np.float64)
    _assert_backends_match(rf.trees_, X)


@settings(max_examples=10, deadline=None)
@given(n_trees=st.integers(1, 6), seed=st.integers(0, 999))
def test_route_batched_random_trees_heavy_padding(n_trees, seed):
    """Hand-built trees of wildly different sizes in one padded ensemble."""
    rng = np.random.default_rng(seed)
    sizes = rng.choice([1, 3, 7, 15, 31, 63], size=n_trees)
    trees = [_random_tree(rng, int(s), d=4) for s in sizes]
    X = rng.normal(size=(50, 4)).astype(np.float32).astype(np.float64)
    _assert_backends_match(trees, X)


def test_route_batched_single_node_forest():
    """All-stump ensemble: max_depth 0, every sample lands in leaf 0."""
    trees = [_single_node_tree() for _ in range(3)]
    X = np.random.default_rng(0).normal(size=(20, 3))
    ta = TreeArrays.from_trees(trees)
    out = route_forest_batched(ta, X)
    np.testing.assert_array_equal(out, np.zeros((20, 3), np.int32))
    np.testing.assert_array_equal(route_forest_batched(ta, X, backend="jax"),
                                  np.zeros((20, 3), np.int32))


def test_route_batched_mixed_stump_and_deep():
    """Padding lanes of the stump must stay inert next to a deep tree."""
    rng = np.random.default_rng(7)
    trees = [_single_node_tree(), _random_tree(rng, 63, d=4),
             _single_node_tree()]
    X = rng.normal(size=(64, 4)).astype(np.float32).astype(np.float64)
    _assert_backends_match(trees, X)


def test_route_batched_nan_features_go_right():
    """NaN fails `x <= thr`, so the oracle sends it right; batched/native
    paths must do the same (not evaluate `x > thr`, which NaN also fails)."""
    rng = np.random.default_rng(11)
    trees = [_random_tree(rng, 31, d=3) for _ in range(4)]
    X = rng.normal(size=(40, 3)).astype(np.float32).astype(np.float64)
    X[::3, 0] = np.nan
    X[1::4, 2] = np.nan
    ta = TreeArrays.from_trees(trees)
    expected = route_forest_numpy(trees, X)
    np.testing.assert_array_equal(
        route_forest_batched(ta, X, backend="numpy"), expected)
    from repro.forest import _native
    if _native.available():
        np.testing.assert_array_equal(
            route_forest_batched(ta, X, backend="native"), expected)


def test_route_batched_exact_threshold_hits():
    """Samples exactly on a split threshold go left (x <= thr)."""
    tr = _random_tree(np.random.default_rng(3), 15, d=2)
    thr = tr.threshold[tr.feature >= 0]
    X = np.zeros((len(thr), 2))
    X[:, 0] = thr.astype(np.float64)
    X[:, 1] = thr.astype(np.float64)
    _assert_backends_match([tr], X)


def _chain_tree(rng: np.random.Generator, depth: int, d: int) -> Tree:
    """A path of ``depth`` splits, each with one leaf child; the path goes
    on to the side that about 90% of standard normal values take, so
    walks run deep."""
    n_nodes = 2 * depth + 1
    feature = np.full(n_nodes, -1, np.int32)
    threshold = np.zeros(n_nodes, np.float32)
    left = np.zeros(n_nodes, np.int32)
    right = np.zeros(n_nodes, np.int32)
    node = 0
    for k in range(depth):
        on, off = 2 * k + 1, 2 * k + 2
        feature[node] = rng.integers(0, d)
        if rng.random() < 0.5:                  # the path goes left
            threshold[node] = np.float32(1.3)
            left[node], right[node] = on, off
        else:
            threshold[node] = np.float32(-1.3)
            left[node], right[node] = off, on
        node = on
    leaves = feature == -1
    leaf_id = np.full(n_nodes, -1, np.int32)
    leaf_id[leaves] = np.arange(leaves.sum(), dtype=np.int32)
    return Tree(feature=feature, threshold=threshold, left=left, right=right,
                leaf_id=leaf_id, value=np.ones((n_nodes, 2), np.float32),
                n_node_samples=np.ones(n_nodes, np.int32), depth=depth)


def _mixed_forest(n_trees: int, d: int, seed: int) -> list:
    """Stumps, bushy trees and deep chains in one padded ensemble."""
    rng = np.random.default_rng(seed)
    kinds = [lambda: _single_node_tree(),
             lambda: _random_tree(rng, 255, d),
             lambda: _chain_tree(rng, 40, d),
             lambda: _random_tree(rng, 15, d)]
    return [kinds[t % len(kinds)]() for t in range(n_trees)]


# 80 features put 2,049 and 4,097 rows over route_forest's 1 MiB row block
# (2 and 3 blocks), so tasks cross row blocks as well as trees.
@pytest.mark.parametrize("threads", [1, 2, 3])
@pytest.mark.parametrize("n_trees", [1, 3, 37])
@pytest.mark.parametrize("n", [1, 7, 2049, 4097])
def test_native_route_tiles_match_oracle(n, n_trees, threads):
    from repro.forest import _native
    if not _native.available():
        pytest.skip("no host C compiler")
    d = 80
    trees = _mixed_forest(n_trees, d, seed=n_trees)
    rng = np.random.default_rng(n)
    X = rng.normal(size=(n, d))
    X[rng.random(size=X.shape) < 0.05] = np.nan
    ta = TreeArrays.from_trees(trees)
    T, M = ta.feature.shape
    got, used = _native.route_native(*ta.flat(), T, M, X, threads=threads)
    np.testing.assert_array_equal(got, route_forest_numpy(trees, X))
    row_block, _ = _native.route_grain(n, T, d, threads)
    assert (row_block < n) == (n > 2000)
    tasks = -(-n // row_block) * T
    assert used == (1 if n * T < _native._SERIAL_WALKS
                    else min(threads, tasks))


def _route_counts():
    from repro.obs.metrics import global_registry
    fam = global_registry().counter("forest_route_total",
                                    labels=("path", "threads"))
    return {k: c.value for k, c in fam.items()}


@pytest.mark.parametrize("n,n_trees,backend,path", [
    (20, 3, "auto", "serial"),         # 60 walks: one thread
    (512, 8, "auto", "tiled"),         # 4,096 walks on 8 tasks
    (20, 3, "numpy", "numpy"),
])
def test_route_counter_follows_shape(n, n_trees, backend, path):
    """``forest_route_total{path,threads}`` counts each call once, on the
    path the shape chose, with the threads that walked the trees."""
    from repro.forest import _native
    if backend == "auto" and not _native.available():
        pytest.skip("no host C compiler")
    rng = np.random.default_rng(n)
    trees = [_random_tree(rng, 31, d=4) for _ in range(n_trees)]
    ta = TreeArrays.from_trees(trees)
    X = rng.normal(size=(n, 4))
    threads = 1
    if path == "tiled":
        threads = min(int(_native._lib.max_threads()), n_trees)
        if threads == 1:
            path = "serial"                # a one-thread host never tiles
    before = _route_counts()
    route_forest_batched(ta, X, backend=backend)
    after = _route_counts()
    moved = {k: v - before.get(k, 0.0) for k, v in after.items()
             if v != before.get(k, 0.0)}
    assert moved == {(path, str(threads)): 1.0}


def test_forest_apply_uses_batched_path(small_cls_data):
    Xtr, ytr, Xte, _ = small_cls_data
    rf = RandomForest(n_trees=6, seed=1).fit(Xtr, ytr)
    np.testing.assert_array_equal(rf.apply(Xte),
                                  route_forest_numpy(rf.trees_, Xte))
    assert rf.tree_arrays() is rf.tree_arrays()   # cached, not rebuilt


def test_parallel_fit_deterministic(small_cls_data):
    Xtr, ytr, _, _ = small_cls_data
    serial = RandomForest(n_trees=6, seed=3, n_jobs=1).fit(Xtr, ytr)
    parallel = RandomForest(n_trees=6, seed=3, n_jobs=4).fit(Xtr, ytr)
    for a, b in zip(serial.trees_, parallel.trees_):
        np.testing.assert_array_equal(a.feature, b.feature)
        np.testing.assert_array_equal(a.threshold, b.threshold)
        np.testing.assert_array_equal(a.leaf_id, b.leaf_id)
