"""TPU-native SWLC ops (segment-sum factorization) vs the naive oracle,
plus spectral layer properties — including hypothesis property tests.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _hyp import given, settings, st   # hypothesis, or deterministic fallback

from repro.core.factorization import naive_swlc
from repro.core.jax_ops import _swlc_product, swlc_bucket, swlc_gather
from repro.core.spectral import LeafPCA, kernel_eigs
from repro.kernels.block_prox.ref import block_prox_ref


def _leafset(rng, n, T, lpt):
    gl = rng.integers(0, lpt, (n, T)) + np.arange(T)[None, :] * lpt
    return gl.astype(np.int32)


def _train_product(gl, q, w, V, total_leaves, t_chunk=None):
    """P V over the training set (queries = references) as one program."""
    gl = jnp.asarray(gl)
    return np.asarray(_swlc_product(gl, jnp.asarray(q), gl, jnp.asarray(w),
                                    jnp.asarray(V), total_leaves, t_chunk))


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 60), T=st.integers(1, 10), lpt=st.integers(1, 6),
       seed=st.integers(0, 999))
def test_swlc_matvec_property(n, T, lpt, seed):
    rng = np.random.default_rng(seed)
    gl = _leafset(rng, n, T, lpt)
    q = rng.random((n, T))
    w = rng.random((n, T))
    v = rng.random(n)
    P = naive_swlc(gl, gl, q, w)
    f32 = lambda a: a.astype(np.float32)
    got = _train_product(gl, f32(q), f32(w), f32(v)[:, None], T * lpt)[:, 0]
    np.testing.assert_allclose(got, P @ v, rtol=2e-4, atol=2e-4)


def test_swlc_matmat_and_block():
    rng = np.random.default_rng(0)
    n, T, lpt = 80, 12, 5
    gl = _leafset(rng, n, T, lpt)
    q = rng.random((n, T)).astype(np.float32)
    w = rng.random((n, T)).astype(np.float32)
    V = rng.random((n, 4)).astype(np.float32)
    P = naive_swlc(gl, gl, q, w)
    got = _train_product(gl, q, w, V, T * lpt)
    np.testing.assert_allclose(got, P @ V, rtol=2e-4, atol=2e-4)
    B = block_prox_ref(jnp.asarray(gl[:16]), jnp.asarray(q[:16]),
                       jnp.asarray(gl), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(B), P[:16], rtol=2e-4, atol=2e-4)


def test_swlc_matmat_tree_chunked_matches_unchunked():
    """t_chunk must not change results for any chunk size (incl. padding)."""
    rng = np.random.default_rng(2)
    n, T, lpt = 50, 7, 4
    gl = _leafset(rng, n, T, lpt)
    q = rng.random((n, T)).astype(np.float32)
    w = rng.random((n, T)).astype(np.float32)
    V = rng.random((n, 3)).astype(np.float32)
    ref = _train_product(gl, q, w, V, T * lpt)
    for tc in (1, 2, 3, 7, 16):
        got = _train_product(gl, q, w, V, T * lpt, t_chunk=tc)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_swlc_matmat_large_C_chunked_regression():
    """At C large enough that an unchunked (N, T, C) intermediate would
    dominate memory (256·64·4096 ≈ 67M elements, ~268 MB f32 — vs ~256 KB
    of factors), the engine's one-tree-per-step product (a (N, 1, C)
    intermediate) must still match the dense oracle."""
    rng = np.random.default_rng(3)
    n, T, lpt, C = 256, 64, 8, 4096
    tc = 1
    gl = _leafset(rng, n, T, lpt)
    q = rng.random((n, T)).astype(np.float32)
    w = rng.random((n, T)).astype(np.float32)
    V = rng.random((n, C)).astype(np.float32)
    P = naive_swlc(gl, gl, q, w)
    got = _train_product(gl, q, w, V, T * lpt, t_chunk=tc)
    np.testing.assert_allclose(got, P @ V, rtol=2e-3, atol=2e-3)


def test_swlc_predict_oos():
    rng = np.random.default_rng(1)
    n, nq, T, lpt = 60, 9, 8, 4
    gl_w = _leafset(rng, n, T, lpt)
    gl_q = _leafset(rng, nq, T, lpt)
    q = rng.random((nq, T)).astype(np.float32)
    w = rng.random((n, T)).astype(np.float32)
    Y = rng.random((n, 3)).astype(np.float32)
    P = naive_swlc(gl_q, gl_w, q, w)
    S = swlc_bucket(jnp.asarray(gl_w), jnp.asarray(w), jnp.asarray(Y),
                    T * lpt)
    got = swlc_gather(jnp.asarray(gl_q), jnp.asarray(q), S)
    np.testing.assert_allclose(np.asarray(got), P @ Y, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("t_chunk", [None, 1, 3])
def test_bucket_then_gather_is_the_fused_product(t_chunk, dtype):
    """The two stages as separate programs give the fused product bit for
    bit (T = 8 is no multiple of 3: the padding bucket is used)."""
    rng = np.random.default_rng(5)
    n, nq, T, lpt = 70, 11, 8, 5
    L = T * lpt
    gl_w, gl_q = _leafset(rng, n, T, lpt), _leafset(rng, nq, T, lpt)
    q, w = rng.random((nq, T)), rng.random((n, T))
    Y = rng.random((n, 3))
    with jax.enable_x64(dtype == np.float64):
        a = [jnp.asarray(x, dtype) for x in (q, w, Y)]
        S = swlc_bucket(jnp.asarray(gl_w), a[1], a[2], L, t_chunk)
        got = np.asarray(swlc_gather(jnp.asarray(gl_q), a[0], S, t_chunk))
        want = np.asarray(_swlc_product(jnp.asarray(gl_q), a[0],
                                        jnp.asarray(gl_w), a[1], a[2], L,
                                        t_chunk))
    assert S.shape == (L + 1, 3) and got.dtype == dtype
    assert not np.asarray(S)[L].any()
    np.testing.assert_array_equal(got, want)
    P = naive_swlc(gl_q, gl_w, q, w)
    np.testing.assert_allclose(got, P @ Y, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------- spectral
def test_leafpca_matches_dense_svd(rf_kernel_cache):
    fk = rf_kernel_cache["kerf"]
    Q = fk.Q_
    pca = LeafPCA(n_components=5).fit(Q)
    Z = pca.transform(Q)
    Qd = np.asarray(Q.todense())
    Qc = Qd - Qd.mean(0)
    _, s, vt = np.linalg.svd(Qc, full_matrices=False)
    # singular values match; coordinates match up to sign
    np.testing.assert_allclose(pca.singular_values_, s[:5], rtol=1e-6)
    Zd = Qc @ vt[:5].T
    for j in range(5):
        c = np.corrcoef(Z[:, j], Zd[:, j])[0, 1]
        assert abs(abs(c) - 1) < 1e-6


def test_kernel_eigs_match_gram(rf_kernel_cache):
    fk = rf_kernel_cache["kerf"]
    vals, vecs = kernel_eigs(fk.Q_, k=4)
    P = np.asarray(fk.kernel(set_diagonal=False).todense())
    ev = np.linalg.eigvalsh(P)[::-1][:4]
    np.testing.assert_allclose(vals, ev, rtol=1e-6, atol=1e-8)


def test_leafpca_oos_transform(rf_kernel_cache):
    fk = rf_kernel_cache["kerf"]
    X, y = rf_kernel_cache["_data"]
    pca = LeafPCA(n_components=4).fit(fk.Q_)
    Zte = pca.transform(fk.query_map(X[:20] + 1e-4))
    Ztr = pca.transform(fk.Q_)[:20]
    # a perturbed training point embeds next to its source
    d = np.linalg.norm(Zte - Ztr, axis=1)
    spread = np.linalg.norm(Ztr - Ztr.mean(0), axis=1).mean()
    assert (d < 0.35 * spread).mean() > 0.9
