"""Pallas kernels vs pure-jnp oracles (interpret mode), with shape sweeps."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _hyp import given, settings, st   # hypothesis, or deterministic fallback

from repro.data.synthetic import gaussian_classes
from repro.forest.ensemble import RandomForest
from repro.kernels.block_prox.ops import block_prox
from repro.kernels.block_prox.ref import block_prox_ref
from repro.kernels.histogram.ops import histogram
from repro.kernels.histogram.ref import histogram_ref
from repro.kernels.leaf_route import ops as route_ops
from repro.kernels.leaf_route.ref import route_ref


# ------------------------------------------------- leaf_route
# (`fitted_forest` is the session-scoped fixture from conftest.py)
def test_route_pallas_matches_numpy(fitted_forest):
    rf, X = fitted_forest
    ta = rf.tree_arrays()
    expected = rf.apply(X)
    got = route_ops.route(X, ta, block_n=128)
    np.testing.assert_array_equal(got, expected)


def test_route_ref_matches_numpy(fitted_forest):
    rf, X = fitted_forest
    ta = rf.tree_arrays()
    got = route_ref(jnp.asarray(X, jnp.float32), jnp.asarray(ta.feature),
                    jnp.asarray(ta.threshold), jnp.asarray(ta.left),
                    jnp.asarray(ta.right), jnp.asarray(ta.leaf_id),
                    ta.max_depth)
    np.testing.assert_array_equal(np.asarray(got), rf.apply(X))


@pytest.mark.parametrize("block_n", [32, 64, 256])
def test_route_block_sizes(fitted_forest, block_n):
    rf, X = fitted_forest
    ta = rf.tree_arrays()
    got = route_ops.route(X[:100], ta, block_n=block_n)
    np.testing.assert_array_equal(got, rf.apply(X[:100]))


# ---------------------------------------------------------------- block_prox
def _rand_leafset(rng, n, T, leaves_per_tree):
    gl = rng.integers(0, leaves_per_tree, (n, T)) + \
        np.arange(T)[None, :] * leaves_per_tree
    return gl.astype(np.int32)


@pytest.mark.parametrize("nq,nw,T", [(64, 64, 8), (100, 50, 16), (17, 200, 5),
                                     (256, 256, 40)])
def test_block_prox_shapes(nq, nw, T):
    rng = np.random.default_rng(nq + nw + T)
    gl_q = _rand_leafset(rng, nq, T, 6)
    gl_w = _rand_leafset(rng, nw, T, 6)
    q = rng.random((nq, T)).astype(np.float32)
    w = rng.random((nw, T)).astype(np.float32)
    got = np.asarray(block_prox(gl_q, q, gl_w, w, block_q=64, block_w=64))
    want = np.asarray(block_prox_ref(jnp.asarray(gl_q), jnp.asarray(q),
                                     jnp.asarray(gl_w), jnp.asarray(w)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_block_prox_padding_no_phantom_collisions():
    """Padding sentinels must never produce collisions."""
    rng = np.random.default_rng(0)
    gl = _rand_leafset(rng, 5, 3, 4)          # tiny, heavy padding
    q = np.ones((5, 3), np.float32)
    got = np.asarray(block_prox(gl, q, gl, q, block_q=64, block_w=64))
    want = np.asarray(block_prox_ref(jnp.asarray(gl), jnp.asarray(q),
                                     jnp.asarray(gl), jnp.asarray(q)))
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("T", [1, 37, 100])
def test_block_prox_tiles_trees(T):
    """The kernel walks the trees in chunks of T_CHUNK, padding T with
    sentinel trees: any T, a multiple of the chunk or not, agrees with the
    oracle (float32, interpret mode)."""
    from repro.kernels.block_prox.block_prox import T_CHUNK
    assert T % T_CHUNK or T == 1
    rng = np.random.default_rng(T)
    gl_q = _rand_leafset(rng, 70, T, 4)
    gl_w = _rand_leafset(rng, 300, T, 4)
    q = rng.random((70, T)).astype(np.float32)
    w = rng.random((300, T)).astype(np.float32)
    got = np.asarray(block_prox(gl_q, q, gl_w, w, dtype=jnp.float32))
    want = np.asarray(block_prox_ref(jnp.asarray(gl_q), jnp.asarray(q),
                                     jnp.asarray(gl_w), jnp.asarray(w)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 5, 40])
def test_block_topk_against_dense_rows(k):
    """The device top-k against reference factors staged in the kernel's
    padded layout: each row's k largest proximities, at indices that
    realize them.  Rows with fewer than k collisions pad with zeros at
    real columns, never at the layout's padding (≥ n_ref)."""
    from repro.core.jax_ops import swlc_block_topk
    from repro.kernels.block_prox import block_prox as bp
    rng = np.random.default_rng(k)
    nq, nw, T = 20, 600, 11
    gl_q, gl_w = _rand_leafset(rng, nq, T, 3), _rand_leafset(rng, nw, T, 3)
    gl_q[:4] += 10 ** 6                     # four rows collide with nothing
    q = rng.random((nq, T)).astype(np.float32)
    w = rng.random((nw, T)).astype(np.float32)
    P = np.asarray(block_prox_ref(jnp.asarray(gl_q), jnp.asarray(q),
                                  jnp.asarray(gl_w), jnp.asarray(w)))
    ref = bp.pad_reference(jnp.asarray(gl_w), jnp.asarray(w),
                           block_w=bp.BLOCK_W, t_chunk=bp.T_CHUNK,
                           dtype=np.dtype(np.float32))
    assert ref[0].shape == (16, 1024)
    v, ix = (np.asarray(a) for a in swlc_block_topk(
        jnp.asarray(gl_q), jnp.asarray(q), *ref, k=k, n_ref=nw,
        interpret=True))
    assert v.shape == ix.shape == (nq, k) and ix.dtype == np.int32
    np.testing.assert_allclose(v, -np.sort(-P, axis=1)[:, :k], rtol=1e-6)
    assert ((ix >= 0) & (ix < nw)).all()
    np.testing.assert_allclose(np.take_along_axis(P, ix, axis=1), v,
                               rtol=1e-6)
    assert (v[:4] == 0).all()


def test_compiled_block_prox_refuses_float64(monkeypatch):
    """Off the CPU the kernel runs compiled, in float32 only: a float64
    request raises instead of silently computing in float32."""
    from repro.kernels.block_prox import ops as bp_ops
    monkeypatch.setattr(bp_ops, "interpret_mode", lambda: False)
    gl = _rand_leafset(np.random.default_rng(0), 4, 3, 4)
    q = np.ones((4, 3))
    with pytest.raises(ValueError, match="float32 only"):
        block_prox(gl, q, gl, q, dtype=jnp.float64)


@settings(max_examples=20, deadline=None)
@given(nq=st.integers(1, 40), nw=st.integers(1, 40), T=st.integers(1, 12),
       seed=st.integers(0, 2 ** 16))
def test_block_prox_property(nq, nw, T, seed):
    rng = np.random.default_rng(seed)
    gl_q = _rand_leafset(rng, nq, T, 3)
    gl_w = _rand_leafset(rng, nw, T, 3)
    q = rng.random((nq, T)).astype(np.float32)
    w = rng.random((nw, T)).astype(np.float32)
    got = np.asarray(block_prox(gl_q, q, gl_w, w, block_q=32, block_w=32))
    want = np.asarray(block_prox_ref(jnp.asarray(gl_q), jnp.asarray(q),
                                     jnp.asarray(gl_w), jnp.asarray(w)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_block_prox_matches_scipy_factorization(rf_kernel_cache):
    """End-to-end: Pallas block == CSR factorization block."""
    fk = rf_kernel_cache["kerf"]
    gl = fk.ctx.global_leaves()
    qw = fk.assignment.query_weights(fk.ctx.leaves)
    sub = np.arange(120)
    got = np.asarray(block_prox(gl[sub], qw[sub], gl[sub], qw[sub]))
    want = fk.kernel_block(sub, sub)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ----------------------------------------------------------------- histogram
@pytest.mark.parametrize("n,d,nodes,bins,C", [
    (300, 6, 4, 16, 3), (1000, 10, 8, 32, 7), (128, 3, 1, 8, 2),
    (513, 5, 100, 16, 4),   # node chunking path
])
def test_histogram_shapes(n, d, nodes, bins, C):
    rng = np.random.default_rng(n + d)
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = rng.integers(0, nodes, n).astype(np.int32)
    y = rng.integers(0, C, n).astype(np.int32)
    w = rng.random(n).astype(np.float32)
    got = np.asarray(histogram(xb, node, y, w, nodes, bins, C, tile=256))
    want = np.asarray(histogram_ref(jnp.asarray(xb), jnp.asarray(node),
                                    jnp.asarray(y), jnp.asarray(w),
                                    nodes, bins, C))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_histogram_total_mass():
    """Σ hist over (bin, class) = Σ weights per node, for every feature."""
    rng = np.random.default_rng(3)
    n, d, nodes, bins, C = 400, 4, 6, 16, 3
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = rng.integers(0, nodes, n).astype(np.int32)
    y = rng.integers(0, C, n).astype(np.int32)
    w = rng.random(n).astype(np.float32)
    h = np.asarray(histogram(xb, node, y, w, nodes, bins, C))
    per_node = np.bincount(node, weights=w, minlength=nodes)
    for f in range(d):
        np.testing.assert_allclose(h[:, f].sum((1, 2)), per_node, rtol=1e-5)


def test_histogram_matches_trainer_bincount():
    """Pallas histogram == the numpy trainer's bincount histogram."""
    rng = np.random.default_rng(5)
    n, d, bins, C = 600, 5, 12, 3
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = rng.integers(0, 3, n).astype(np.int32)
    y = rng.integers(0, C, n).astype(np.int32)
    w = np.ones(n, np.float32)
    flat = ((node[:, None] * d + np.arange(d)[None, :]) * bins + xb) * C + y[:, None]
    want = np.bincount(flat.ravel(), weights=np.repeat(w, d),
                       minlength=3 * d * bins * C).reshape(3, d, bins, C)
    got = np.asarray(histogram(xb, node, y, w, 3, bins, C))
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ------------------------------------------------- histogram wrapper bugfixes
from repro.kernels.histogram import ops as hist_ops
from repro.kernels.histogram.histogram import (DEFAULT_VMEM_BUDGET,
                                               hist_vmem_bytes,
                                               histogram_pallas)
from repro.kernels.histogram.ops import moments
from repro.kernels.histogram.ref import moments_ref


def _int_fixture(n, d, nodes, bins, C, seed=0):
    """Integer-weight fixture: float32 accumulation is exact, so chunked
    vs unchunked comparisons can demand bit-equality."""
    rng = np.random.default_rng(seed)
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = rng.integers(0, nodes, n).astype(np.int32)
    y = rng.integers(0, C, n).astype(np.int32)
    w = rng.integers(0, 4, n).astype(np.float32)
    return xb, node, y, w


@pytest.mark.parametrize("nodes,max_chunk", [
    (65, 64),    # the one-past-boundary case: a 64-node chunk + a 1-node tail
    (64, 64),    # exactly one chunk (no chunking)
    (130, 64),   # 3 chunks, ragged tail
    (100, 17),   # ragged everywhere
])
def test_histogram_node_chunking_equals_unchunked(nodes, max_chunk):
    xb, node, y, w = _int_fixture(700, 5, nodes, 16, 3, seed=nodes)
    chunked = np.asarray(histogram(xb, node, y, w, nodes, 16, 3, tile=256,
                                   max_node_chunk=max_chunk))
    whole = np.asarray(histogram(xb, node, y, w, nodes, 16, 3, tile=256,
                                 max_node_chunk=nodes + 1))
    np.testing.assert_array_equal(chunked, whole)


def test_node_chunking_scans_each_sample_once(monkeypatch):
    """The chunked path must pre-partition samples: total samples fed to
    the kernel across chunks equals N (+ tile padding), not N x chunks."""
    xb, node, y, w = _int_fixture(1000, 4, 130, 8, 3, seed=11)
    seen = []
    orig = hist_ops.histogram_pallas

    def spy(xb_c, *a, **k):
        seen.append(int(xb_c.shape[0]))
        return orig(xb_c, *a, **k)

    monkeypatch.setattr(hist_ops, "histogram_pallas", spy)
    hist_ops.histogram(xb, node, y, w, 130, 8, 3, tile=256, max_node_chunk=64)
    assert len(seen) == 3                       # ceil(130 / 64) chunks
    # each chunk is tile-padded, so the bound is N + chunks * (tile - 1)
    assert sum(seen) <= 1000 + 3 * 255, seen


def test_histogram_feature_chunking_small_budget():
    """A vmem budget too small for all features at once still gives the
    full-width answer (feature axis is chunked and re-concatenated)."""
    xb, node, y, w = _int_fixture(500, 11, 10, 16, 3, seed=4)
    # the wrapper buckets the 10 nodes to 16
    budget = hist_vmem_bytes(256, 3, 16, 16, 3) + 1
    got = np.asarray(histogram(xb, node, y, w, 10, 16, 3, tile=256,
                               vmem_budget=budget))
    whole = np.asarray(histogram(xb, node, y, w, 10, 16, 3, tile=256))
    np.testing.assert_array_equal(got, whole)


def test_histogram_pallas_vmem_guard():
    """The kernel itself refuses blocks that exceed the VMEM budget."""
    xb, node, y, w = _int_fixture(600, 8, 4096, 256, 10, seed=5)
    with pytest.raises(ValueError, match="VMEM"):
        histogram_pallas(jnp.asarray(xb), jnp.asarray(node), jnp.asarray(y),
                         jnp.asarray(w), 4096, 256, 10, tile=512,
                         interpret=True)
    # the ops wrapper sizes blocks to fit the same budget and succeeds
    out = histogram(xb, node, y, w, 4096, 256, 10, tile=512)
    assert out.shape == (4096, 8, 256, 10)


def test_histogram_empty_input_is_zero():
    """Zero samples must give a zero histogram (the raw pallas_call with a
    zero-length grid never runs its init step)."""
    h = np.asarray(histogram(np.zeros((0, 3), np.int32),
                             np.zeros(0, np.int32), np.zeros(0, np.int32),
                             np.zeros(0, np.float32), 5, 8, 2))
    assert h.shape == (5, 3, 8, 2) and not h.any()


def test_interpret_resolution_probes_lowering(monkeypatch):
    """interpret=None picks interpret mode on the CPU backend only — any
    other backend compiles (and a refused compile raises) — and an
    explicit caller override wins."""
    assert hist_ops.resolve_interpret(None) is True
    assert hist_ops.resolve_interpret(False) is False
    assert hist_ops.resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert hist_ops.resolve_interpret(None) is False


# ----------------------------------------------------------------- moments
def test_moments_matches_ref():
    rng = np.random.default_rng(7)
    n, d, nodes, bins, K = 800, 6, 9, 16, 3
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = rng.integers(0, nodes, n).astype(np.int32)
    wm = rng.random((n, K)).astype(np.float32)
    got = np.asarray(moments(xb, node, wm, nodes, bins, tile=256))
    want = np.asarray(moments_ref(jnp.asarray(xb), jnp.asarray(node),
                                  jnp.asarray(wm), nodes, bins, K))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_moments_node_chunking_boundary():
    rng = np.random.default_rng(8)
    n, d, nodes, bins = 600, 4, 65, 8
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = rng.integers(0, nodes, n).astype(np.int32)
    wm = rng.integers(0, 4, (n, 3)).astype(np.float32)
    chunked = np.asarray(moments(xb, node, wm, nodes, bins, tile=256,
                                 max_node_chunk=64))
    whole = np.asarray(moments(xb, node, wm, nodes, bins, tile=256,
                               max_node_chunk=nodes + 1))
    np.testing.assert_array_equal(chunked, whole)


# ----------------------------------- kernel vs trainer production oracle
def test_histogram_matches_trainer_hist_numpy_weighted():
    """Weighted class histograms vs training.py::_hist_numpy — the pallas
    path checked against the production oracle, not just histogram_ref."""
    from repro.forest.training import _hist_numpy
    rng = np.random.default_rng(9)
    n, d, nodes, bins, C = 900, 6, 7, 16, 4
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = np.sort(rng.integers(0, nodes, n)).astype(np.int32)
    y = rng.integers(0, C, n).astype(np.int32)
    w = rng.random(n)
    bounds = np.searchsorted(node, np.arange(nodes + 1)).astype(np.int64)
    want = _hist_numpy(xb.astype(np.uint8), np.arange(n, dtype=np.int64),
                       w, y.astype(np.int64), bounds, d, bins, C, True)
    got = np.asarray(histogram(xb, node, y, w.astype(np.float32),
                               nodes, bins, C, tile=256))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_moments_match_trainer_hist_numpy_regression():
    """(Σw, Σwy, Σwy²) moments vs the trainer's regression histogram."""
    from repro.forest.training import _hist_numpy
    rng = np.random.default_rng(10)
    n, d, nodes, bins = 700, 5, 6, 16
    xb = rng.integers(0, bins, (n, d)).astype(np.int32)
    node = np.sort(rng.integers(0, nodes, n)).astype(np.int32)
    yr = rng.random(n)
    w = rng.integers(1, 4, n).astype(np.float64)
    bounds = np.searchsorted(node, np.arange(nodes + 1)).astype(np.int64)
    want = _hist_numpy(xb.astype(np.uint8), np.arange(n, dtype=np.int64),
                       w, yr, bounds, d, bins, 3, False)
    wm = np.stack([w, w * yr, w * yr * yr], axis=1).astype(np.float32)
    got = np.asarray(moments(xb, node, wm, nodes, bins, tile=256))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
