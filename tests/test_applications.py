"""Proximity applications vs explicit dense oracles (P = Q Wᵀ, ≤200 samples),
scipy/pallas backend agreement, determinism, and the no-dense-P guard.
"""
import numpy as np
import pytest

from repro.applications.embed import ProximityEmbedding
from repro.applications.imputation import ProximityImputer
from repro.applications.outliers import outlier_scores
from repro.applications.propagate import propagate_labels
from repro.applications.prototypes import (NearestPrototypeClassifier,
                                           select_prototypes)

BACKENDS = ["scipy", "pallas"]


# ------------------------------------------------------------------ outliers
def test_outlier_scores_dense_oracle(app_kernel_cache):
    P = app_kernel_cache["P"]
    _, y = app_kernel_cache["_data"]
    counts = np.bincount(y)
    own = np.array([(P[i, y == y[i]] ** 2).sum() for i in range(len(y))])
    with np.errstate(divide="ignore"):
        raw_ref = np.minimum(counts[y] / own, float(len(y)) ** 2)
    for be in BACKENDS:
        raw = outlier_scores(app_kernel_cache[be].engine, y, normalize=False)
        np.testing.assert_allclose(raw, raw_ref, rtol=1e-10, atol=1e-10)
    # normalized scores: per-class median 0, backends agree
    norm = {be: outlier_scores(app_kernel_cache[be].engine, y)
            for be in BACKENDS}
    for c in range(3):
        assert abs(np.median(norm["scipy"][y == c])) < 1e-12
    for be in BACKENDS[1:]:
        np.testing.assert_allclose(norm[be], norm["scipy"], atol=1e-8)


def test_outlier_scores_flag_mislabeled_points(app_kernel_cache):
    """Points relabeled into a foreign class have tiny within-class
    proximities — their scores must stand out."""
    _, y = app_kernel_cache["_data"]
    rng = np.random.default_rng(0)
    planted = rng.choice(np.flatnonzero(y == 0), size=4, replace=False)
    y_mod = y.copy()
    y_mod[planted] = 1
    s = outlier_scores(app_kernel_cache["scipy"].engine, y_mod)
    assert s[planted].min() > np.percentile(s, 75)
    assert s[planted].mean() > s.mean() + 1.0


def test_forestkernel_outlier_surface(app_kernel_cache):
    fk = app_kernel_cache["scipy"]
    s = fk.outlier_scores()
    assert s.shape == (fk.ctx.n_train,)
    np.testing.assert_allclose(
        s, outlier_scores(fk.engine, fk.ctx.y), atol=1e-12)


# ---------------------------------------------------------------- imputation
def _knockout(X, frac, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random(X.shape) < frac
    Xm = X.copy()
    Xm[mask] = np.nan
    return Xm, mask


def test_imputation_beats_rough_fill(app_kernel_cache):
    X, y = app_kernel_cache["_data"]
    Xm, mask = _knockout(X, 0.1, seed=3)
    imp = ProximityImputer(n_iter=2, kernel_kwargs=dict(
        kernel_method="gap", n_trees=10, seed=0))
    Xi = imp.fit_transform(Xm, y)
    assert np.isfinite(Xi).all()
    # observed entries untouched
    np.testing.assert_array_equal(Xi[~mask], X[~mask])
    err = np.abs(Xi[mask] - X[mask]).mean()
    med = np.nanmedian(Xm, axis=0)
    err_med = np.abs(np.broadcast_to(med, X.shape)[mask] - X[mask]).mean()
    assert err < 0.8 * err_med, (err, err_med)
    assert len(imp.history_) >= 1


def test_imputation_categorical_votes(app_kernel_cache):
    X, y = app_kernel_cache["_data"]
    # append a label-derived categorical column, knock out 25% of it
    Xc = np.concatenate([X, y[:, None].astype(np.float64)], axis=1)
    rng = np.random.default_rng(4)
    miss = rng.random(len(y)) < 0.25
    Xm = Xc.copy()
    Xm[miss, -1] = np.nan
    imp = ProximityImputer(n_iter=2, categorical=(Xc.shape[1] - 1,),
                           kernel_kwargs=dict(kernel_method="gap",
                                              n_trees=10, seed=0))
    Xi = imp.fit_transform(Xm, y)
    codes = Xi[miss, -1]
    assert set(np.unique(codes)) <= set(np.unique(y).astype(np.float64))
    acc = (codes == y[miss]).mean()
    base = np.bincount(y[~miss]).max() / (~miss).sum()   # mode fill
    assert acc > max(0.6, base), (acc, base)


def test_imputation_deterministic(app_kernel_cache):
    X, y = app_kernel_cache["_data"]
    Xm, _ = _knockout(X, 0.1, seed=5)
    kw = dict(kernel_method="gap", n_trees=8, seed=0)
    a = ProximityImputer(n_iter=2, kernel_kwargs=kw).fit_transform(Xm, y)
    b = ProximityImputer(n_iter=2, kernel_kwargs=kw).fit_transform(Xm, y)
    np.testing.assert_array_equal(a, b)


def test_imputation_no_missing_passthrough(app_kernel_cache):
    X, y = app_kernel_cache["_data"]
    imp = ProximityImputer(kernel_kwargs=dict(n_trees=5, seed=0))
    np.testing.assert_array_equal(imp.fit_transform(X, y), X)
    assert imp.history_ == []


def test_forestkernel_impute_surface(app_kernel_cache):
    from repro.core.api import ForestKernel
    X, y = app_kernel_cache["_data"]
    Xm, mask = _knockout(X, 0.08, seed=6)
    imp = ForestKernel(kernel_method="gap", n_trees=8, seed=0) \
        .impute(Xm, y, n_iter=1)
    assert np.isfinite(imp.X_imputed_).all()
    assert imp.missing_mask_.sum() == mask.sum()
    assert imp.kernel_.n_trees == 8     # refits inherit the config


# ---------------------------------------------------------------- prototypes
def test_prototypes_class_membership_and_agreement(app_kernel_cache):
    _, y = app_kernel_cache["_data"]
    ref = None
    for be in ["scipy", "pallas"]:
        protos, cov = select_prototypes(app_kernel_cache[be].engine, y,
                                        n_prototypes=3, k=40)
        for c, ids in protos.items():
            assert 1 <= len(ids) <= 3
            assert (y[ids] == c).all()
            assert 0 < cov[c] <= 1
        if ref is None:
            ref = protos
        else:
            for c in ref:
                np.testing.assert_array_equal(protos[c], ref[c])


def test_prototypes_deterministic(app_kernel_cache):
    _, y = app_kernel_cache["_data"]
    eng = app_kernel_cache["scipy"].engine
    a, _ = select_prototypes(eng, y, n_prototypes=4, k=30)
    b, _ = select_prototypes(eng, y, n_prototypes=4, k=30)
    for c in a:
        np.testing.assert_array_equal(a[c], b[c])


def test_nearest_prototype_classifier(app_kernel_cache):
    X, y = app_kernel_cache["_data"]
    clf = NearestPrototypeClassifier(n_prototypes=3, k=40) \
        .fit(app_kernel_cache["scipy"].engine, y)
    acc = (clf.predict() == y).mean()
    assert acc > 0.85, acc
    # OOS queries route through the engine's cached query states
    yq = clf.predict(X[:25] + 1e-3)
    assert (yq == y[:25]).mean() > 0.85
    # decision_function is dense only over the prototype columns
    B = clf.decision_function(block=64)
    assert B.shape == (len(y), len(clf.prototype_indices_))


def test_forestkernel_prototypes_surface(app_kernel_cache):
    fk = app_kernel_cache["scipy"]
    protos, cov = fk.prototypes(n_prototypes=2, k=30)
    assert set(protos) == {0, 1, 2}


# ----------------------------------------------------------------- propagate
def _propagate_dense(P, y, labeled, n_classes, alpha, n_iter, tol):
    """Literal dense replica of the factored iteration."""
    S = P / np.maximum(P.sum(1, keepdims=True), np.finfo(np.float64).tiny)
    Y0 = np.zeros((len(y), n_classes))
    Y0[labeled, y[labeled]] = 1.0
    F = Y0.copy()
    for _ in range(n_iter):
        Fn = alpha * (S @ F) + (1 - alpha) * Y0
        Fn[labeled] = Y0[labeled]
        delta = float(np.abs(Fn - F).max())
        F = Fn
        if delta < tol:
            break
    scores = F / np.maximum(F.sum(1, keepdims=True),
                            np.finfo(np.float64).tiny)
    return F.argmax(1), scores


def test_propagate_dense_oracle_all_backends(app_kernel_cache):
    P = app_kernel_cache["P"]
    _, y = app_kernel_cache["_data"]
    rng = np.random.default_rng(7)
    labeled = rng.random(len(y)) < 0.15
    ref_lab, ref_scores = _propagate_dense(P, y, labeled, 3, 0.8, 30, 1e-5)
    for be in BACKENDS:
        lab, scores = propagate_labels(app_kernel_cache[be].engine, y,
                                       labeled, alpha=0.8, n_iter=30,
                                       tol=1e-5)
        np.testing.assert_array_equal(lab, ref_lab)
        np.testing.assert_allclose(scores, ref_scores, atol=1e-8)


def test_propagate_recovers_labels_and_clamps(app_kernel_cache):
    _, y = app_kernel_cache["_data"]
    rng = np.random.default_rng(8)
    labeled = rng.random(len(y)) < 0.15
    y_obs = np.where(labeled, y, -1)         # unlabeled entries are ignored
    lab, scores = propagate_labels(app_kernel_cache["scipy"].engine, y_obs,
                                   labeled)
    np.testing.assert_array_equal(lab[labeled], y[labeled])
    assert (lab[~labeled] == y[~labeled]).mean() > 0.8
    np.testing.assert_allclose(scores.sum(1), 1.0, atol=1e-12)


def test_forestkernel_propagate_surface(app_kernel_cache):
    fk = app_kernel_cache["scipy"]
    labeled = np.zeros(fk.ctx.n_train, dtype=bool)
    labeled[::5] = True
    lab, _ = fk.propagate_labels(labeled)
    assert lab.shape == (fk.ctx.n_train,)


# --------------------------------------------------------------------- embed
def test_embed_matches_dense_eigendecomposition(app_kernel_cache):
    """Symmetric kernel: Z Zᵀ must equal the best rank-k approximation of
    the dense oracle P."""
    fk = app_kernel_cache["sym"]
    P = app_kernel_cache["P_sym"]
    k = 4
    emb = ProximityEmbedding(n_components=k).fit(fk.engine)
    vals = np.linalg.eigvalsh(P)[::-1][:k]
    np.testing.assert_allclose(emb.eigvals_, vals, rtol=1e-8, atol=1e-10)
    w, U = np.linalg.eigh(P)
    Pk = (U[:, -k:] * w[-k:]) @ U[:, -k:].T
    np.testing.assert_allclose(emb.embedding_ @ emb.embedding_.T, Pk,
                               atol=1e-6)


def test_embed_nystrom_reproduces_training_rows(app_kernel_cache):
    """Re-querying the training points OOS must land on the training
    embedding exactly (symmetric method: OOS weights = training weights)."""
    fk = app_kernel_cache["sym"]
    X, _ = app_kernel_cache["_data"]
    emb = ProximityEmbedding(n_components=3).fit(fk.engine)
    Z_oos = emb.transform(X[:30])
    np.testing.assert_allclose(Z_oos, emb.embedding_[:30], atol=1e-8)


def test_embed_asymmetric_operator_path(app_kernel_cache):
    """GAP (q ≠ w) goes through the symmetrized factored operator; the
    (query-side, approximate — see embed.py docstring) Nyström transform
    agrees across engine backends."""
    emb = ProximityEmbedding(n_components=3).fit(
        app_kernel_cache["scipy"].engine)
    assert np.isfinite(emb.embedding_).all()
    assert (np.diff(emb.eigvals_) <= 1e-12).all()
    X, _ = app_kernel_cache["_data"]
    Z_ref = emb.transform(X[:20] + 1e-3)
    emb.engine_ = app_kernel_cache["pallas"].engine
    np.testing.assert_allclose(emb.transform(X[:20] + 1e-3), Z_ref,
                               atol=1e-8)


def test_embed_leafpca_path(app_kernel_cache):
    fk = app_kernel_cache["sym"]
    X, _ = app_kernel_cache["_data"]
    emb = ProximityEmbedding(n_components=3, method="leafpca").fit(fk.engine)
    assert emb.embedding_.shape == (len(X), 3)
    # mean-centered coordinates
    np.testing.assert_allclose(emb.embedding_.mean(0), 0, atol=1e-8)
    # training points re-queried OOS land on their training coords
    np.testing.assert_allclose(emb.transform(X[:20]), emb.embedding_[:20],
                               atol=1e-8)


def test_embed_deterministic(app_kernel_cache):
    eng = app_kernel_cache["sym"].engine
    a = ProximityEmbedding(n_components=3, seed=1).fit(eng).embedding_
    b = ProximityEmbedding(n_components=3, seed=1).fit(eng).embedding_
    np.testing.assert_array_equal(a, b)


def test_forestkernel_embed_surface(app_kernel_cache):
    fk = app_kernel_cache["sym"]
    emb = fk.embed(n_components=2)
    assert emb.embedding_.shape == (fk.ctx.n_train, 2)


# ------------------------------------------------- acceptance: no dense P ---
BLOCK = 64


def test_applications_never_densify_P(app_kernel_cache, monkeypatch):
    """Acceptance guard: run every workload with full_kernel forbidden and
    all dense-block/matmat shapes instrumented — P is never materialized
    beyond a ≤BLOCK-row streaming chunk, on scipy and pallas backends."""
    from repro.core import factorization
    from repro.core.engine import ProximityEngine

    X, y = app_kernel_cache["_data"]
    shapes = {"block_rows": 0, "matmat_cols": 0}

    def forbidden(*a, **k):
        raise AssertionError("dense/full P materialized")

    orig_block = ProximityEngine.kernel_block
    orig_matmat = ProximityEngine.matmat

    def spy_block(self, rows=None, cols=None, X_rows=None):
        n_rows = self.query_state(X_rows).Q.shape[0] if rows is None \
            else len(np.asarray(rows))
        shapes["block_rows"] = max(shapes["block_rows"], n_rows)
        return orig_block(self, rows, cols, X_rows=X_rows)

    def spy_matmat(self, V, X=None, col_mask=None, normalized=False):
        shapes["matmat_cols"] = max(shapes["matmat_cols"],
                                    np.asarray(V).shape[1])
        return orig_matmat(self, V, X=X, col_mask=col_mask,
                           normalized=normalized)

    monkeypatch.setattr(ProximityEngine, "full_kernel", forbidden)
    monkeypatch.setattr(factorization, "full_kernel", forbidden)
    monkeypatch.setattr(ProximityEngine, "kernel_block", spy_block)
    monkeypatch.setattr(ProximityEngine, "matmat", spy_matmat)

    for be in ["scipy", "pallas"]:
        eng = app_kernel_cache[be].engine
        outlier_scores(eng, y, block=BLOCK)
        propagate_labels(eng, y, y >= 0, n_iter=5)
        clf = NearestPrototypeClassifier(n_prototypes=2, k=20).fit(eng, y)
        clf.predict(block=BLOCK)
        clf.predict(X[:10] + 1e-3, block=BLOCK)
        emb = ProximityEmbedding(n_components=2).fit(eng)
        emb.transform(X[:10] + 1e-3)
    # imputation refits internally; give it a fresh small config
    Xm, _ = _knockout(X, 0.05, seed=9)
    ProximityImputer(n_iter=1, kernel_kwargs=dict(
        kernel_method="gap", n_trees=6, seed=0)).fit_transform(Xm, y)

    assert 0 < shapes["block_rows"] <= BLOCK, shapes
    assert 0 < shapes["matmat_cols"] <= 32, shapes
