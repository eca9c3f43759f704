import numpy as np
import pytest

from repro.data.synthetic import gaussian_classes, train_test_split


@pytest.fixture(scope="session")
def small_cls_data():
    X, y = gaussian_classes(1200, d=12, n_classes=4, seed=7)
    return train_test_split(X, y, test_frac=0.2, seed=7)


@pytest.fixture(scope="session")
def rf_kernel_cache():
    """One fitted ForestKernel per kernel_method, shared across tests.

    The forest is fitted ONCE and shared: every ForestKernel reuses the same
    trees and only rebuilds its (cheap) weight factors, so the session pays a
    single training run instead of one per kernel method.
    """
    from repro.core.api import ForestKernel
    X, y = gaussian_classes(900, d=10, n_classes=3, seed=3)
    out = {}
    shared_forest = None
    for method in ["original", "kerf", "oob", "gap"]:
        fk = ForestKernel(kernel_method=method, n_trees=15, seed=0)
        if shared_forest is None:
            fk.fit(X, y)
            shared_forest = fk.forest
        else:
            fk.forest = shared_forest
            fk.build_kernel_cache()
        out[method] = fk
    out["_data"] = (X, y)
    return out


def _engine_backends():
    from repro.forest import _native
    return ["scipy", "pallas"] + (["native"] if _native.available() else [])


@pytest.fixture(scope="session")
def method_kernel_cache():
    """Per weight assignment (``core.weights.ASSIGNMENTS``): one small
    (180-sample) ForestKernel per engine backend, all on one shared
    forest, plus the explicit dense oracle P = Q Wᵀ from the scipy factors
    and the data it was fitted on.

    The five random-forest assignments share one 12-tree forest fitted
    once; ``boosted`` gets a 12-tree depth-4 gradient-boosted forest on
    two-class data (boosted classification is binary).
    """
    from repro.core.api import ForestKernel
    from repro.core.weights import ASSIGNMENTS
    rf_data = gaussian_classes(180, d=8, n_classes=3, sep=3.0, seed=5)
    gbt_data = gaussian_classes(180, d=8, n_classes=2, sep=3.0, seed=5)
    forests = {}          # by whether the forest is boosted
    out = {}
    for method in ASSIGNMENTS:
        boosted = method == "boosted"
        kw = dict(model_type="gbt", max_depth=4, max_features=None) \
            if boosted else {}
        data = gbt_data if boosted else rf_data
        cache = {}
        for be in _engine_backends():
            fk = ForestKernel(kernel_method=method, n_trees=12, seed=0,
                              engine_backend=be, **kw)
            if boosted in forests:
                fk.forest = forests[boosted]
                fk.build_kernel_cache()
            else:
                forests[boosted] = fk.fit(*data).forest
            cache[be] = fk
        cache["P"] = np.asarray(
            (cache["scipy"].Q_ @ cache["scipy"].W_.T).todense())
        cache["_data"] = data
        out[method] = cache
    return out


@pytest.fixture(scope="session")
def app_kernel_cache(method_kernel_cache):
    """The ``gap`` kernels of ``method_kernel_cache`` on every engine
    backend and their dense oracle 'P' — the fixture for the
    engine-primitive and proximity-application tests.

    'sym' is the symmetric-method (original) scipy kernel on the same
    forest for the spectral/embedding tests, with its own oracle 'P_sym'.
    """
    gap, sym = method_kernel_cache["gap"], method_kernel_cache["original"]
    out = {be: gap[be] for be in _engine_backends()}
    out.update(sym=sym["scipy"], P=gap["P"], P_sym=sym["P"],
               _data=gap["_data"])
    return out


@pytest.fixture(scope="session")
def fitted_forest():
    """Small fitted RandomForest + its training data, shared session-wide."""
    from repro.forest.ensemble import RandomForest
    X, y = gaussian_classes(800, d=10, n_classes=3, seed=0)
    rf = RandomForest(n_trees=8, seed=0).fit(X, y)
    return rf, X
