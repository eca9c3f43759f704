"""ProximityEngine backend equivalence: scipy vs jax vs pallas.

The acceptance bar: predict / topk / kernel_block / matvec must agree with
the scipy CSR reference path to atol 1e-8 on every backend, with no per-tree
Python loop on any call path.
"""
import numpy as np
import pytest

from repro.core.engine import ENGINE_BACKENDS, ProximityEngine
from repro.forest import _native

BACKENDS = [be for be in ENGINE_BACKENDS
            if be != "native" or _native.available()]
NON_SCIPY = tuple(be for be in BACKENDS if be != "scipy")


def _engines(rf_kernel_cache, method):
    """One engine per backend sharing one fitted context — no refits."""
    fk = rf_kernel_cache[method]
    out = {"scipy": fk.engine}
    for be in NON_SCIPY:
        out[be] = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                                  backend=be)
    return fk, out


@pytest.mark.parametrize("method", ["original", "gap"])
def test_predict_identical_across_backends(rf_kernel_cache, method):
    fk, engines = _engines(rf_kernel_cache, method)
    y = fk.ctx.y
    C = fk.forest.n_classes_
    ref = engines["scipy"].predict(y, n_classes=C)
    for be in NON_SCIPY:
        got = engines[be].predict(y, n_classes=C)
        np.testing.assert_allclose(got, ref, atol=1e-8)


@pytest.mark.parametrize("method", ["original", "gap"])
def test_oos_predict_identical_across_backends(rf_kernel_cache, method):
    fk, engines = _engines(rf_kernel_cache, method)
    X, y = rf_kernel_cache["_data"]
    Xq = X[:25] + 1e-3
    ref = engines["scipy"].predict(y, n_classes=fk.forest.n_classes_, X=Xq)
    for be in NON_SCIPY:
        got = engines[be].predict(y, n_classes=fk.forest.n_classes_, X=Xq)
        np.testing.assert_allclose(got, ref, atol=1e-8)


def test_topk_identical_across_backends(rf_kernel_cache):
    fk, engines = _engines(rf_kernel_cache, "original")
    _, val_ref = engines["scipy"].topk(k=5)
    P = np.asarray(fk.kernel(set_diagonal=False).todense())
    for be in BACKENDS:
        idx, val = engines[be].topk(k=5)
        np.testing.assert_allclose(val, val_ref, atol=1e-8)
        # reported indices must realize the reported proximities
        np.testing.assert_allclose(
            np.take_along_axis(P, idx, axis=1), val, atol=1e-8)


@pytest.mark.parametrize("model_type,method", [("rf", "original"),
                                               ("gbt", "boosted")])
def test_pallas_oos_topk_matches_scipy(model_type, method):
    """Pallas top-k of held-out rows (the tiled kernel and the selection on
    the device, interpret mode, float32 as on the chip) against scipy's:
    the same values to 1e-6 relative, and every returned index realizes
    its value (ties at rank k may pick other rows)."""
    from repro.core.api import ForestKernel
    from repro.data.synthetic import gaussian_classes
    X, y = gaussian_classes(700, d=6, n_classes=2, sep=1.0, seed=21)
    fk = ForestKernel(model_type=model_type, kernel_method=method,
                      n_trees=13, max_depth=5, seed=0,
                      max_features="sqrt" if model_type == "rf" else None,
                      dtype=np.float32, engine_backend="scipy").fit(X, y)
    pallas = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                             backend="pallas", dtype=np.float32)
    Xq = X[:37] + 0.05
    idx_ref, val_ref = fk.engine.topk(k=7, X=Xq)
    idx, val = pallas.topk(k=7, X=Xq)
    assert idx.shape == val.shape == (37, 7) and val.dtype == np.float32
    np.testing.assert_allclose(val, val_ref, rtol=1e-6)
    P = np.asarray(fk.engine.kernel_block(X_rows=Xq))
    np.testing.assert_allclose(np.take_along_axis(P, idx, axis=1), val,
                               rtol=1e-6)
    assert all(len(set(r)) == 7 for r in idx)


def test_kernel_block_identical_across_backends(rf_kernel_cache):
    fk, engines = _engines(rf_kernel_cache, "gap")
    rows, cols = np.arange(40), np.arange(10, 90)
    ref = engines["scipy"].kernel_block(rows, cols)
    for be in NON_SCIPY:
        np.testing.assert_allclose(engines[be].kernel_block(rows, cols),
                                   ref, atol=1e-8)


def test_matvec_matmat_identical_across_backends(rf_kernel_cache):
    fk, engines = _engines(rf_kernel_cache, "gap")
    rng = np.random.default_rng(0)
    v = rng.normal(size=fk.ctx.n_train)
    V = rng.normal(size=(fk.ctx.n_train, 3))
    ref_v = engines["scipy"].matvec(v)
    ref_V = engines["scipy"].matmat(V)
    for be in NON_SCIPY:
        np.testing.assert_allclose(engines[be].matvec(v), ref_v, atol=1e-8)
        np.testing.assert_allclose(engines[be].matmat(V), ref_V, atol=1e-8)
    op = engines["jax"].operator()
    np.testing.assert_allclose(op @ v, ref_v, atol=1e-8)


def test_oos_query_state_cached(rf_kernel_cache):
    fk = rf_kernel_cache["original"]
    X, _ = rf_kernel_cache["_data"]
    Xq = X[:15] + 5e-4
    s1 = fk.engine.query_state(Xq)
    s2 = fk.engine.query_state(Xq.copy())      # same content, new buffer
    assert s1 is s2, "OOS query state must be served from cache"
    assert fk.query_map(Xq) is s1.Q


def test_oos_cache_eviction(rf_kernel_cache):
    fk = rf_kernel_cache["original"]
    X, _ = rf_kernel_cache["_data"]
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          oos_cache_size=2)
    batches = [X[:10] + i * 1e-3 for i in range(1, 5)]
    states = [eng.query_state(b) for b in batches]
    assert eng.query_state(batches[-1]) is states[-1]
    assert len(eng._oos_cache) == 2
    # evicted batch is rebuilt, not crashed
    assert eng.query_state(batches[0]) is not states[0]


def test_engine_rejects_unknown_backend(rf_kernel_cache):
    fk = rf_kernel_cache["original"]
    with pytest.raises(ValueError, match="unknown engine backend"):
        ProximityEngine(fk.ctx, fk.assignment, backend="torch")


def test_block_dtype_float32_only_for_compiled_pallas(rf_kernel_cache,
                                                     monkeypatch):
    """Off the CPU the pallas engine asks block_prox for float32 and says
    so; every other backend's block ops compute in the engine dtype."""
    import repro.kernels as kernels
    fk = rf_kernel_cache["gap"]
    build = lambda be: ProximityEngine(fk.ctx, fk.assignment,
                                       forest=fk.forest, backend=be)
    assert build("pallas").block_dtype == np.float64      # interpret mode
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    assert build("pallas").block_dtype == np.float32
    assert build("jax").block_dtype == np.float64


def test_op_paths_count_the_host_cutover(rf_kernel_cache, monkeypatch):
    """Training-set topk / squared row sums above the cutover run as host
    CSR on a device backend and are counted as ``host_cutover``."""
    from repro.obs.metrics import global_registry
    fk = rf_kernel_cache["gap"]
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          backend="jax")
    fam = global_registry().counter(
        "engine_op_path_total", labels=("op", "backend", "path"))
    count = lambda op, path: fam.labels(op=op, backend="jax",
                                        path=path).value
    before = {op: (count(op, "device"), count(op, "host_cutover"))
              for op in ("topk", "squared_row_sums")}
    eng.topk(k=3)
    eng.squared_row_sums()
    monkeypatch.setattr(ProximityEngine, "_SPARSE_TRAIN_CUTOVER", 10)
    eng.topk(k=3)
    eng.squared_row_sums()
    for op, (dev, cut) in before.items():
        assert count(op, "device") == dev + 1, op
        assert count(op, "host_cutover") == cut + 1, op


def test_full_kernel_diagonal_without_lil(rf_kernel_cache):
    """Diagonal override keeps CSR structure and exact values (satellite)."""
    import scipy.sparse as sp
    fk = rf_kernel_cache["oob"]
    P = fk.kernel(set_diagonal=True)
    assert sp.isspmatrix_csr(P)
    np.testing.assert_allclose(P.diagonal(), 1.0)
    # off-diagonal entries untouched
    P0 = fk.kernel(set_diagonal=False)
    D = P - sp.diags(P.diagonal())
    D0 = P0 - sp.diags(P0.diagonal())
    assert abs(D - D0).max() < 1e-12


def test_memory_bytes_accounts_dense_factors(rf_kernel_cache):
    fk = rf_kernel_cache["gap"]
    mb = fk.engine.memory_bytes()
    assert mb["dense_factors"] > 0 and mb["Q"] > 0 and mb["W"] > 0
    assert mb["total"] == sum(v for k, v in mb.items() if k != "total")


# ------------------- applications primitives (dense oracle, ≤200 samples) ---
def test_row_sums_dense_oracle_all_backends(app_kernel_cache):
    P = app_kernel_cache["P"]
    X, _ = app_kernel_cache["_data"]
    Xq = X[:20] + 1e-3
    Pq = np.asarray((app_kernel_cache["scipy"].query_map(Xq) @
                     app_kernel_cache["scipy"].W_.T).todense())
    for be in BACKENDS:
        eng = app_kernel_cache[be].engine
        np.testing.assert_allclose(eng.row_sums(), P.sum(1), atol=1e-8)
        np.testing.assert_allclose(eng.row_sums(X=Xq), Pq.sum(1), atol=1e-8)
    # training row sums are cached
    eng = app_kernel_cache["scipy"].engine
    assert eng.row_sums() is eng.row_sums()


def test_masked_matmat_dense_oracle_all_backends(app_kernel_cache):
    P = app_kernel_cache["P"]
    rng = np.random.default_rng(0)
    V = rng.normal(size=(P.shape[1], 4))
    mask = rng.random(P.shape[1]) < 0.5
    ref = P @ (V * mask[:, None])
    for be in BACKENDS:
        got = app_kernel_cache[be].engine.matmat(V, col_mask=mask)
        np.testing.assert_allclose(got, ref, atol=1e-8)


def test_normalized_matmat_dense_oracle_all_backends(app_kernel_cache):
    P = app_kernel_cache["P"]
    rng = np.random.default_rng(1)
    V = rng.normal(size=(P.shape[1], 3))
    ref = (P / P.sum(1)[:, None]) @ V
    for be in BACKENDS:
        got = app_kernel_cache[be].engine.matmat(V, normalized=True)
        np.testing.assert_allclose(got, ref, atol=1e-8)


def test_squared_row_sums_dense_oracle_all_backends(app_kernel_cache):
    P = app_kernel_cache["P"]
    X, y = app_kernel_cache["_data"]
    per_class = np.stack([(P[:, y == c] ** 2).sum(1) for c in range(3)], 1)
    Xq = X[:17] + 1e-3
    Pq = np.asarray((app_kernel_cache["scipy"].query_map(Xq) @
                     app_kernel_cache["scipy"].W_.T).todense())
    per_class_q = np.stack([(Pq[:, y == c] ** 2).sum(1) for c in range(3)], 1)
    for be in BACKENDS:
        eng = app_kernel_cache[be].engine
        # odd block size exercises the streaming chunk boundaries
        np.testing.assert_allclose(eng.squared_row_sums(block=53),
                                   (P ** 2).sum(1), atol=1e-8)
        np.testing.assert_allclose(
            eng.squared_row_sums(class_ids=y, block=53), per_class,
            atol=1e-8)
        np.testing.assert_allclose(
            eng.squared_row_sums(class_ids=y, X=Xq, block=7), per_class_q,
            atol=1e-8)


# --------------------------------------------- sharded matmat (satellite) ---
def test_sharded_matmat_single_device_fallback(app_kernel_cache):
    """On one device default_mesh() gates off and matmat takes the segment
    path, still agreeing with scipy."""
    import jax
    from repro.core.jax_ops import default_mesh
    if jax.device_count() > 1:
        pytest.skip("requires a single-device jax runtime")
    assert default_mesh() is None
    eng = app_kernel_cache["jax"].engine
    rng = np.random.default_rng(2)
    V = rng.normal(size=(eng.W.shape[0], 3))
    ref = app_kernel_cache["scipy"].engine.matmat(V)
    np.testing.assert_allclose(eng.matmat(V), ref, atol=1e-8)
    assert eng.last_matmat_path == "segment"


@pytest.mark.slow
def test_engine_sharded_matmat_multi_device():
    """Forced 8-host-device subprocess: the train-state jax matmat routes
    through sharded_swlc_matmat and agrees with scipy; OOS batches fall back
    to the segment path."""
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(repo, "src"))
    code = textwrap.dedent("""
        import numpy as np
        from repro.core.api import ForestKernel
        from repro.data.synthetic import gaussian_classes
        # 162 rows: not a multiple of the 8 devices, so the sharded path
        # pads its rows
        X, y = gaussian_classes(162, d=8, n_classes=3, seed=5)
        fk = ForestKernel(kernel_method="gap", n_trees=10, seed=0,
                          engine_backend="jax").fit(X, y)
        ref = ForestKernel(kernel_method="gap", n_trees=10, seed=0)
        ref.forest = fk.forest
        ref.build_kernel_cache()
        V = np.random.default_rng(0).normal(size=(162, 3))
        np.testing.assert_allclose(fk.engine.matmat(V),
                                   ref.engine.matmat(V), atol=1e-8)
        assert fk.engine.last_matmat_path == "sharded", \\
            fk.engine.last_matmat_path
        Xq = X[:21] + 1e-3
        np.testing.assert_allclose(fk.engine.matmat(V, X=Xq),
                                   ref.engine.matmat(V, X=Xq), atol=1e-8)
        assert fk.engine.last_matmat_path == "segment"
        # wide V splits into sharded column chunks (forced tiny budget)
        from repro.core import jax_ops
        orig = jax_ops.auto_c_chunk
        jax_ops.auto_c_chunk = lambda *a, **k: 3
        W = np.random.default_rng(1).normal(size=(162, 10))
        np.testing.assert_allclose(fk.engine.matmat(W),
                                   ref.engine.matmat(W), atol=1e-8)
        assert fk.engine.last_matmat_path == "sharded"
        jax_ops.auto_c_chunk = orig
        print("SHARDED ENGINE OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SHARDED ENGINE OK" in r.stdout


# ------------------------------------------ reference bucket tables (cache) --
def _ref_counts(backend):
    from repro.obs.metrics import global_registry
    fam = global_registry().counter("engine_ref_table_total",
                                    labels=("backend", "result"))
    return {r: fam.labels(backend=backend, result=r).value
            for r in ("hit", "miss", "uncached")}


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


def _count_buckets(monkeypatch):
    """Calls of ``jax_ops.swlc_bucket``, the device bucket stage."""
    from repro.core import jax_ops
    calls = []
    real = jax_ops.swlc_bucket

    def counted(*a, **k):
        calls.append(a[2].shape)
        return real(*a, **k)
    monkeypatch.setattr(jax_ops, "swlc_bucket", counted)
    return calls


def _fused_predict(eng, y, C, X=None):
    """The single fused program of both stages (``swlc_predict``), with
    the engine's own inputs and the self-term removed for X=None."""
    import jax
    import jax.numpy as jnp
    from repro.core import jax_ops
    qs = eng.query_state(X)
    Y, _ = eng._label_table(y, C)
    with jax.enable_x64(True):
        out = np.asarray(jax_ops.swlc_predict(
            *(jnp.asarray(a) for a in (qs.gl, qs.q, eng.gl, eng.w, Y)),
            eng.total_leaves, t_chunk=1))
    if X is None:
        out = out - (qs.q * eng.w).sum(axis=1)[:, None] * Y
    return out


@pytest.mark.parametrize("oos", [False, True])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_device_ref_table_predict_is_the_fused_product(rf_kernel_cache,
                                                       backend, oos):
    """With S built once and kept on the device, predict gives the fused
    bucket-and-gather program bit for bit, cold and warm, and scipy to
    1e-12 (OOS, and the training set with ``exclude_self``)."""
    fk = rf_kernel_cache["gap"]
    X, y = rf_kernel_cache["_data"]
    C = fk.forest.n_classes_
    Xq = X[:40] + 2e-3 if oos else None
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          backend=backend)
    want = _fused_predict(eng, y, C, Xq)
    cold = eng.predict(y, n_classes=C, X=Xq)
    warm = eng.predict(y, n_classes=C, X=Xq)
    np.testing.assert_array_equal(cold, want)
    np.testing.assert_array_equal(warm, want)
    ref = fk.engine.predict(y, n_classes=C, X=Xq)
    np.testing.assert_allclose(warm, ref, rtol=0, atol=1e-12)


@pytest.mark.parametrize("backend", BACKENDS)
def test_second_predict_hits_the_ref_table(rf_kernel_cache, backend,
                                           monkeypatch):
    """The same labels a second time: one ``hit``, and on a device backend
    no bucket stage is dispatched; the table is a device array there."""
    import jax
    fk = rf_kernel_cache["original"]
    X, y = rf_kernel_cache["_data"]
    C = fk.forest.n_classes_
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          backend=backend)
    buckets = _count_buckets(monkeypatch)
    before = _ref_counts(backend)
    first = eng.predict(y, n_classes=C, X=X[:20] + 1e-3)
    mid = _ref_counts(backend)
    second = eng.predict(y, n_classes=C, X=X[20:50] + 1e-3)
    assert _delta(before, mid) == {"hit": 0, "miss": 1, "uncached": 0}
    assert _delta(mid, _ref_counts(backend)) == \
        {"hit": 1, "miss": 0, "uncached": 0}
    assert (eng.ref_cache_hits, eng.ref_cache_misses) == (1, 1)
    (_, S), = eng._ref_cache.values()
    on_device = backend in ("jax", "pallas")
    assert isinstance(S, jax.Array) == on_device
    assert buckets == ([(len(y), C)] if on_device else [])
    np.testing.assert_allclose(
        np.concatenate([first, second]),
        fk.engine.predict(y, n_classes=C,
                          X=np.concatenate([X[:20], X[20:50]]) + 1e-3),
        atol=1e-12)


@pytest.mark.parametrize("how", ["wide", "budget_chunked"])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_uncached_ref_tables_stay_correct(rf_kernel_cache, backend, how,
                                          monkeypatch):
    """Wide V (33 columns) and the column chunks of a memory budget are
    built once per call and never cached; answers match scipy."""
    fk = rf_kernel_cache["gap"]
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          backend=backend)
    rng = np.random.default_rng(8)
    if how == "wide":
        V, chunks = rng.normal(size=(eng.W.shape[0], 33)), 1
    else:
        V, chunks = rng.normal(size=(eng.W.shape[0], 3)), 3
        monkeypatch.setattr(eng, "_col_chunk", lambda n_cols: 1)
    buckets = _count_buckets(monkeypatch)
    before = _ref_counts(backend)
    for _ in range(2):
        np.testing.assert_allclose(eng.matmat(V), fk.engine.matmat(V),
                                   atol=1e-12)
    assert _delta(before, _ref_counts(backend)) == \
        {"hit": 0, "miss": 0, "uncached": 2 * chunks}
    assert len(buckets) == 2 * chunks
    assert not eng._ref_cache and eng._ref_cache_bytes == 0


def test_byte_budget_evicts_device_ref_tables(rf_kernel_cache):
    """Device tables count their bytes against the cache's byte budget:
    room for one table keeps only the newest."""
    fk = rf_kernel_cache["original"]
    X, y = rf_kernel_cache["_data"]
    C = fk.forest.n_classes_
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          backend="jax")
    table = (eng.total_leaves + 1) * C * 8
    eng._ref_cache_byte_budget = table + table // 2
    y2 = np.roll(y, 1)
    Xq = X[:10] + 1e-3
    eng.predict(y, n_classes=C, X=Xq)
    eng.predict(y2, n_classes=C, X=Xq)
    assert len(eng._ref_cache) == 1 and eng._ref_cache_bytes == table
    (key, (_, S)), = eng._ref_cache.items()
    assert key == eng._label_table(y2, C)[1] and S.nbytes == table
    # the evicted table is built again, and the answer is unchanged
    got = eng.predict(y, n_classes=C, X=Xq)
    assert (eng.ref_cache_hits, eng.ref_cache_misses) == (0, 3)
    np.testing.assert_allclose(got, fk.engine.predict(y, n_classes=C, X=Xq),
                               atol=1e-12)


def test_two_label_arrays_get_two_device_tables(rf_kernel_cache):
    fk = rf_kernel_cache["gap"]
    X, y = rf_kernel_cache["_data"]
    C = fk.forest.n_classes_
    eng = ProximityEngine(fk.ctx, fk.assignment, forest=fk.forest,
                          backend="pallas")
    y2 = (np.asarray(y) + 1) % C
    Xq = X[:30] + 1e-3
    got = [eng.predict(lab, n_classes=C, X=Xq) for lab in (y, y2, y, y2)]
    assert len(eng._ref_cache) == 2
    assert (eng.ref_cache_hits, eng.ref_cache_misses) == (2, 2)
    a, b = (np.asarray(S) for _, S in eng._ref_cache.values())
    assert a.shape == b.shape == (eng.total_leaves + 1, C)
    assert not np.array_equal(a, b)
    for lab, out in zip((y, y2, y, y2), got):
        np.testing.assert_allclose(
            out, fk.engine.predict(lab, n_classes=C, X=Xq), atol=1e-12)
