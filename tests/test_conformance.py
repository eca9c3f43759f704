"""Backend × weight assignment × op × side conformance matrix for the
ProximityEngine.

Every backend {scipy, pallas, native} must agree with a **dense numpy
oracle** (P materialized from the CSR factors) to atol 1e-8 on every engine
op {matvec, matmat, predict, topk, row_sums, squared_row_sums, kernel_block}
under each of the six weight assignments {original, kerf, oob, gap, ih,
boosted}, for both training-set and out-of-sample query batches.  This is
the acceptance gate for any new backend, op or assignment: one parametrized
matrix, no backend-specific carve-outs.

Property tests (``_hyp`` shim: hypothesis when installed, deterministic
fallback otherwise) push the same agreement through degenerate forests —
stumps, single-leaf trees, duplicated training rows — and empty OOS batches.
"""
import numpy as np
import pytest

from repro.core.api import ForestKernel
from repro.core.context import EnsembleContext
from repro.core.engine import (ENGINE_BACKENDS, PrefixProximityEngine,
                               ProximityEngine)
from repro.core.weights import ASSIGNMENTS, get_assignment
from repro.data.synthetic import gaussian_classes
from repro.forest import _native

from _hyp import given, settings, st

BACKENDS = [be for be in ENGINE_BACKENDS
            if be != "native" or _native.available()]
SIDES = ("train", "oos")


# --------------------------------------------------------------- oracles ---
def _dense(M) -> np.ndarray:
    return np.asarray(M.todense())


def _oracle(cache, side):
    """Dense proximity oracle for the requested query side + its X batch."""
    if side == "train":
        return cache["P"], None
    X, _ = cache["_data"]
    Xq = np.ascontiguousarray(X[:23] + 1e-3)
    scipy_fk = cache["scipy"]
    Pq = _dense(scipy_fk.query_map(Xq) @ scipy_fk.W_.T)
    return Pq, Xq


# ------------------------------------------------------------- op checks ---
def _check_matvec(eng, P, y, X):
    v = np.random.default_rng(11).normal(size=P.shape[1])
    np.testing.assert_allclose(eng.matvec(v, X=X), P @ v, atol=1e-8)


def _check_matmat(eng, P, y, X):
    V = np.random.default_rng(12).normal(size=(P.shape[1], 3))
    np.testing.assert_allclose(eng.matmat(V, X=X), P @ V, atol=1e-8)


def _check_predict(eng, P, y, X):
    C = int(y.max()) + 1
    Y = np.zeros((len(y), C))
    Y[np.arange(len(y)), y] = 1.0
    got = eng.predict(y, n_classes=C, X=X, exclude_self=False)
    np.testing.assert_allclose(got, P @ Y, atol=1e-8)


def _check_topk(eng, P, y, X):
    idx, val = eng.topk(k=5, X=X)
    ref = -np.sort(-P, axis=1)[:, :5]
    np.testing.assert_allclose(val, ref, atol=1e-8)
    # reported indices must realize the reported proximities
    np.testing.assert_allclose(np.take_along_axis(P, idx, axis=1), val,
                               atol=1e-8)


def _check_row_sums(eng, P, y, X):
    np.testing.assert_allclose(eng.row_sums(X=X), P.sum(axis=1), atol=1e-8)


def _check_squared_row_sums(eng, P, y, X):
    np.testing.assert_allclose(eng.squared_row_sums(X=X, block=17),
                               (P ** 2).sum(axis=1), atol=1e-8)
    C = int(y.max()) + 1
    per = np.stack([(P[:, y == c] ** 2).sum(axis=1) for c in range(C)], 1)
    got = eng.squared_row_sums(class_ids=y, n_classes=C, X=X, block=17)
    np.testing.assert_allclose(got, per, atol=1e-8)


def _check_kernel_block(eng, P, y, X):
    rows = np.arange(3, P.shape[0], 2)
    cols = np.arange(5, P.shape[1], 3)
    if X is None:
        got = eng.kernel_block(rows, cols)
    else:
        got = eng.kernel_block(rows, cols, X_rows=X)
    np.testing.assert_allclose(got, P[np.ix_(rows, cols)], atol=1e-8)
    # full-width block (cols=None)
    got = eng.kernel_block(rows, X_rows=X) if X is not None else \
        eng.kernel_block(rows)
    np.testing.assert_allclose(got, P[rows], atol=1e-8)


OPS = {
    "matvec": _check_matvec,
    "matmat": _check_matmat,
    "predict": _check_predict,
    "topk": _check_topk,
    "row_sums": _check_row_sums,
    "squared_row_sums": _check_squared_row_sums,
    "kernel_block": _check_kernel_block,
}


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("op", sorted(OPS))
@pytest.mark.parametrize("kernel_method", tuple(ASSIGNMENTS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_conformance_matrix(method_kernel_cache, backend, kernel_method, op,
                            side):
    cache = method_kernel_cache[kernel_method]
    eng = cache[backend].engine
    _, y = cache["_data"]
    P, X = _oracle(cache, side)
    OPS[op](eng, P, y, X)


# --------------------------------------------------- empty OOS batches ----
@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_oos_batch(app_kernel_cache, backend):
    """A (0, d) query batch must flow through every op, returning (0, ...)
    results — the serving layer admits whatever the queue holds."""
    eng = app_kernel_cache[backend].engine
    _, y = app_kernel_cache["_data"]
    C = int(y.max()) + 1
    n = eng.W.shape[0]
    X0 = np.zeros((0, app_kernel_cache["_data"][0].shape[1]))
    V = np.random.default_rng(0).normal(size=(n, 2))
    assert eng.matmat(V, X=X0).shape == (0, 2)
    assert eng.predict(y, n_classes=C, X=X0).shape == (0, C)
    assert eng.row_sums(X=X0).shape == (0,)
    assert eng.squared_row_sums(class_ids=y, n_classes=C, X=X0).shape == (0, C)
    idx, val = eng.topk(k=3, X=X0)
    assert idx.shape == (0, 3) and val.shape == (0, 3)


# ------------------------------------------------- depth-prefix tiers -----
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_prefix_factorization_exact(app_kernel_cache, depth):
    """The depth-k prefix engine (leaf contraction of the fitted factors,
    no re-routing) must match a dense oracle built the expensive way: trees
    truncated at depth k, the training set re-routed through them, and a
    fresh engine fitted on that context — atol 1e-8, train and OOS sides."""
    parent = app_kernel_cache["scipy"].engine
    X, _ = app_kernel_cache["_data"]
    pe = PrefixProximityEngine(parent, depth)

    trunc = parent.forest.truncated(depth)
    ctx_o = EnsembleContext.from_forest(trunc, X=parent.ctx.X, y=parent.ctx.y)
    oracle = ProximityEngine(ctx_o, get_assignment(parent.assignment.name,
                                                   ctx_o),
                             forest=trunc, backend="scipy")
    # contracted leaves == re-routed leaves (prefix routing is a prefix)
    np.testing.assert_array_equal(pe.ctx.leaves, ctx_o.leaves)
    P_o = _dense(oracle.Q @ oracle.W.T)
    np.testing.assert_allclose(_dense(pe.Q @ pe.W.T), P_o, atol=1e-8)

    Xq = np.ascontiguousarray(X[:23] + 1e-3)
    Pq_o = _dense(oracle.query_state(Xq).Q @ oracle.W.T)
    np.testing.assert_allclose(_dense(pe.query_state(Xq).Q @ pe.W.T),
                               Pq_o, atol=1e-8)
    # engine ops go through the contracted factors too
    y = parent.ctx.y
    C = int(y.max()) + 1
    np.testing.assert_allclose(pe.predict(y, n_classes=C, X=Xq),
                               oracle.predict(y, n_classes=C, X=Xq),
                               atol=1e-8)
    _, val_p = pe.topk(k=5, X=Xq)
    np.testing.assert_allclose(val_p, -np.sort(-Pq_o, axis=1)[:, :5],
                               atol=1e-8)


@pytest.mark.parametrize("backend", BACKENDS)
def test_prefix_engine_backends_agree(app_kernel_cache, backend):
    """Every backend serves the contracted factors identically."""
    parent = app_kernel_cache["scipy"].engine
    X, y = app_kernel_cache["_data"]
    pe = PrefixProximityEngine(parent, 3)
    ref = _dense(pe.Q @ pe.W.T)
    if backend == "scipy":
        eng = pe
    else:
        eng = ProximityEngine(pe.ctx, pe.assignment, forest=pe.forest,
                              backend=backend)
    V = np.random.default_rng(5).normal(size=(ref.shape[1], 3))
    np.testing.assert_allclose(eng.matmat(V), ref @ V, atol=1e-8)


# ------------------------------------------------- degenerate forests -----
def _fit_engines(X, y, **kw):
    """One shared forest, one engine per backend (tiny configs only)."""
    kw.setdefault("n_trees", 4)
    kw.setdefault("kernel_method", "gap")
    fk = ForestKernel(seed=0, n_jobs=1, **kw).fit(X, y)
    engines = {"scipy": fk.engine}
    for be in BACKENDS:
        if be != "scipy":
            engines[be] = ProximityEngine(fk.ctx, fk.assignment,
                                          forest=fk.forest, backend=be)
    return fk, engines


def _assert_all_backends_conform(engines, y, Xq):
    """Dense-oracle agreement on matmat/predict/topk, train + OOS."""
    scipy_eng = engines["scipy"]
    P = _dense(scipy_eng.Q @ scipy_eng.W.T)
    Pq = _dense(scipy_eng.query_state(Xq).Q @ scipy_eng.W.T)
    rng = np.random.default_rng(3)
    V = rng.normal(size=(P.shape[1], 2))
    C = int(y.max()) + 1
    for be, eng in engines.items():
        np.testing.assert_allclose(eng.matmat(V), P @ V, atol=1e-8,
                                   err_msg=f"{be} train matmat")
        np.testing.assert_allclose(eng.matmat(V, X=Xq), Pq @ V, atol=1e-8,
                                   err_msg=f"{be} oos matmat")
        got = eng.predict(y, n_classes=C, X=Xq)
        Y = np.zeros((len(y), C))
        Y[np.arange(len(y)), y] = 1.0
        np.testing.assert_allclose(got, Pq @ Y, atol=1e-8,
                                   err_msg=f"{be} oos predict")
        idx, val = eng.topk(k=3, X=Xq)
        np.testing.assert_allclose(val, -np.sort(-Pq, axis=1)[:, :3],
                                   atol=1e-8, err_msg=f"{be} oos topk")


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 999))
def test_conformance_stump_forest(seed):
    """Depth-1 trees: two leaves per tree, heavy leaf collisions."""
    X, y = gaussian_classes(60, d=4, n_classes=2, seed=seed)
    _, engines = _fit_engines(X, y, max_depth=1)
    Xq = np.random.default_rng(seed).normal(size=(9, 4))
    _assert_all_backends_conform(engines, y, Xq)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999))
def test_conformance_single_leaf_forest(seed):
    """min_samples_split > N forces root-only leaves: every sample collides
    in the single leaf of every tree."""
    X, y = gaussian_classes(40, d=3, n_classes=2, seed=seed)
    fk, engines = _fit_engines(X, y, min_samples_leaf=50)
    assert fk.ctx.total_leaves == fk.n_trees, "expected single-leaf trees"
    Xq = np.random.default_rng(seed + 1).normal(size=(5, 3))
    _assert_all_backends_conform(engines, y, Xq)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999))
def test_conformance_duplicate_rows(seed):
    """Duplicated training rows: identical rows must produce identical
    kernel rows on every backend (and still match the oracle)."""
    rng = np.random.default_rng(seed)
    Xb, yb = gaussian_classes(30, d=4, n_classes=2, seed=seed)
    dup = rng.integers(0, 30, size=30)
    X = np.concatenate([Xb, Xb[dup]])
    y = np.concatenate([yb, yb[dup]])
    _, engines = _fit_engines(X, y)
    Xq = np.concatenate([Xb[:4], Xb[:4]])        # duplicated OOS rows too
    _assert_all_backends_conform(engines, y, Xq)
    for be, eng in engines.items():
        B = eng.kernel_block(np.arange(8), X_rows=Xq)
        np.testing.assert_allclose(B[:4], B[4:], atol=1e-12,
                                   err_msg=f"{be} duplicate query rows")
