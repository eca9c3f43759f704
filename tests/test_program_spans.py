"""Spans and device name scopes inside the program, on the JAX profiler's
clock: ``repro.obs.trace.span`` events from the engine (routing, upload,
dispatch, fetch) and the ``bucket`` / ``gather`` scopes of the segment
product in the compiled HLO."""
import glob
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import jax_ops
from repro.core.api import ForestKernel
from repro.data.synthetic import gaussian_classes
from repro.obs.trace import SPAN_PREFIX, span

ROUTE = ["engine.batch_key", "engine.apply", "engine.weights",
         "engine.leaf_map"]
DEVICE = ["engine.upload", "engine.dispatch", "engine.fetch"]


def _program_events(tmp_path, fn):
    """Run ``fn`` under a profiler session; the ``repro:`` host events as
    (name, start_ns, end_ns, stats dict), by start."""
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def fitted():
    X, y = gaussian_classes(300, d=6, n_classes=3, sep=3.0, seed=11)
    rng = np.random.default_rng(4)
    batches = [X[rng.choice(len(X), 40)] + 1e-3 * (i + 1) for i in range(4)]
    return X, y, batches


def _kernel(fitted, backend):
    X, y, _ = fitted
    return ForestKernel(kernel_method="gap", n_trees=8, seed=0,
                        engine_backend=backend).fit(X, y)


def test_span_carries_stats(tmp_path):
    with span("outside"):               # no profiler session: a no-op
        pass

    def body():
        with span("x", bytes=123, kind="a"):
            pass
    ev = _program_events(tmp_path, body)
    names = [e[0] for e in ev]
    assert names == ["x"]
    assert ev[0][3]["bytes"] == 123 and ev[0][3]["kind"] == "a"


@pytest.mark.parametrize("backend", ["pallas"])
def test_predict_spans_once_per_call_in_order(fitted, tmp_path, backend):
    fk = _kernel(fitted, backend)
    eng, (_, y, batches) = fk.engine, fitted
    n, T = eng.gl.shape
    nq = len(batches[1])
    assert eng.gl.dtype == np.int64 and eng.w.dtype == np.float64
    # the first call (compiles, labels memo) builds the label table S once,
    # inside engine.ref_table: reference (gl, w) and labels staged there
    first = _program_events(tmp_path / "first", lambda: eng.predict(
        y, n_classes=3, X=batches[0]))
    (_, ra, rb, rstats), = [e for e in first if e[0] == "engine.ref_table"]
    assert rstats == {}
    ups = [(a, b, st["bytes"]) for name, a, b, st in first
           if name == "engine.upload"]
    inside = [nb for a, b, nb in ups if ra <= a and b <= rb]
    assert inside == [2 * n * T * 8 + n * 3 * 8]
    assert [nb for a, b, nb in ups if b <= ra or a >= rb] == \
        [2 * len(batches[0]) * T * 8]
    ev = _program_events(
        tmp_path, lambda: [eng.predict(y, n_classes=3, X=b)
                           for b in batches[1:3]])
    order = ROUTE + DEVICE
    assert [e[0] for e in ev] == order * 2
    # siblings in call order: each ends before the next starts
    for a, b in zip(ev, ev[1:]):
        assert a[2] <= b[1]
    # a warmed call stages the query side (gl, q) only: S stays on the device
    for e in ev:
        if e[0] == "engine.upload":
            assert e[3]["bytes"] == 2 * nq * T * 8


def test_cache_hit_skips_routing_spans(fitted, tmp_path):
    fk = _kernel(fitted, "pallas")
    eng, (_, y, batches) = fk.engine, fitted
    eng.predict(y, n_classes=3, X=batches[0])
    ev = _program_events(tmp_path, lambda: eng.predict(y, n_classes=3,
                                                       X=batches[0]))
    assert [e[0] for e in ev] == ["engine.batch_key"] + DEVICE


@pytest.mark.parametrize("backend,op", [("pallas", "kernel_block")])
def test_block_op_upload_bytes(fitted, tmp_path, backend, op):
    """The pallas ``block_prox`` wrapper of ``kernel_block`` stages its own
    inputs: no ``engine.upload`` span, and its ``engine.dispatch`` span
    carries the bytes of the host arrays it is handed."""
    fk = _kernel(fitted, backend)
    eng, (_, _, batches) = fk.engine, fitted
    Xq = batches[1]
    run = lambda: eng.kernel_block(X_rows=Xq)
    want = run()
    ev = _program_events(tmp_path, run)
    np.testing.assert_array_equal(run(), want)
    n, T = eng.gl.shape
    nq = len(Xq)
    ups = [e[3]["bytes"] for e in ev if e[0] == "engine.upload"]
    dispatch = [e[3] for e in ev if e[0] == "engine.dispatch"]
    fetches = sum(e[0] == "engine.fetch" for e in ev)
    assert eng.gl.dtype == np.int64 and eng.w.dtype == np.float64
    assert ups == [] and fetches == 1
    assert dispatch == [{"bytes": (nq + n) * T * 16}]


def test_pallas_topk_stages_the_reference_once(fitted, tmp_path):
    """The pallas top-k stages its reference factors once per engine, in
    an ``engine.ref_factors`` span whose bytes are those uploaded (int32
    ids and block-dtype weights): the first call counts a miss in
    ``engine_ref_factors_total``, every later call a hit, and a warmed
    call uploads only the query side, n_q·T·(4 + itemsize) bytes."""
    from repro.obs.metrics import global_registry
    eng, (_, _, batches) = _kernel(fitted, "pallas").engine, fitted
    n, T = eng.gl.shape
    size = eng.block_dtype.itemsize
    fam = global_registry().counter("engine_ref_factors_total",
                                    labels=("backend", "result"))
    count = lambda r: fam.labels(backend="pallas", result=r).value
    before = count("miss"), count("hit")
    first = _program_events(tmp_path / "first",
                            lambda: eng.topk(k=5, X=batches[0]))
    (_, _, _, stats), = [e for e in first if e[0] == "engine.ref_factors"]
    assert stats == {"bytes": n * T * (4 + size)}
    assert (count("miss"), count("hit")) == (before[0] + 1, before[1])
    ev = _program_events(tmp_path, lambda: [eng.topk(k=5, X=b)
                                            for b in batches[1:3]])
    assert [e[0] for e in ev] == (ROUTE + DEVICE) * 2
    for e in ev:
        if e[0] == "engine.upload":
            assert e[3]["bytes"] == len(batches[1]) * T * (4 + size)
    assert (count("miss"), count("hit")) == (before[0] + 1, before[1] + 2)


def test_topk_hlo_carries_block_and_select_scopes():
    """The pallas top-k program names its two stages in the ``op_name``
    metadata a device trace reports: the kernel under ``block``, the
    selection under ``select``."""
    from repro.kernels.block_prox import block_prox as bp
    rng = np.random.default_rng(2)
    nq, nw, T = 9, 300, 11
    gl_w = jnp.asarray(rng.integers(0, 4, (nw, T)), jnp.int32)
    w = jnp.asarray(rng.random((nw, T)), jnp.float32)
    ref = bp.pad_reference(gl_w, w, block_w=bp.BLOCK_W, t_chunk=bp.T_CHUNK,
                           dtype=np.dtype(np.float32))
    text = jax_ops.swlc_block_topk.lower(
        jnp.asarray(rng.integers(0, 4, (nq, T)), jnp.int32),
        jnp.asarray(rng.random((nq, T)), jnp.float32), *ref, k=3, n_ref=nw,
        interpret=True).compile().as_text()
    paths = [p.split("/") for p in _op_names(text)]
    assert any("block" in p for p in paths)
    assert any("select" in p for p in paths)


def test_span_without_jax(fitted, monkeypatch):
    """Where JAX is not installed a span is a no-op, so a host engine's
    out-of-sample queries run as before."""
    from repro.obs import trace
    fk = _kernel(fitted, "scipy")
    eng, (_, y, batches) = fk.engine, fitted
    want = eng.predict(y, n_classes=3, X=batches[1])
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    trace._annotation.cache_clear()
    try:
        with pytest.raises(ImportError):
            import jax.profiler  # noqa: F401
        with span("engine.upload", bytes=1):
            pass
        qs = eng.query_state(batches[2])
        assert qs.gl.shape == (len(batches[2]), eng.gl.shape[1])
        np.testing.assert_array_equal(
            eng.predict(y, n_classes=3, X=batches[1]), want)
        assert trace._annotation() is None
    finally:
        trace._annotation.cache_clear()


def _op_names(hlo_text):
    return [line.split('op_name="')[1].split('"')[0]
            for line in hlo_text.splitlines() if 'op_name="' in line]


@pytest.mark.parametrize("t_chunk", [None, 1, 3])
def test_product_hlo_carries_stage_scopes(t_chunk):
    """The compiled segment product names its bucket and gather stages in
    the ``op_name`` metadata the device trace reports (one-shot, one tree
    per step, and chunked with padding)."""
    rng = np.random.default_rng(0)
    nw, nq, T, C, L = 30, 7, 5, 2, 40
    gl_w = jnp.asarray(rng.integers(0, L, (nw, T)), jnp.int32)
    gl_q = jnp.asarray(rng.integers(0, L, (nq, T)), jnp.int32)
    w = jnp.asarray(rng.random((nw, T)), jnp.float32)
    q = jnp.asarray(rng.random((nq, T)), jnp.float32)
    V = jnp.asarray(rng.random((nw, C)), jnp.float32)
    text = jax_ops._swlc_product.lower(gl_q, q, gl_w, w, V, total_leaves=L,
                                       t_chunk=t_chunk).compile().as_text()
    paths = [p.split("/") for p in _op_names(text)]
    assert any("bucket" in p for p in paths)
    assert any("gather" in p for p in paths)
    # the scatter is the bucket stage's, the row gather the gather stage's
    for line in text.splitlines():
        if " scatter(" in line:
            assert "/bucket/" in line, line
        if " gather(" in line:
            assert "/gather/" in line, line


def test_sharded_product_hlo_carries_stage_scopes():
    rng = np.random.default_rng(1)
    n, T, C, L = 16, 4, 3, 32
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    args = (jnp.asarray(rng.integers(0, L, (n, T)), jnp.int32),
            jnp.asarray(rng.random((n, T)), jnp.float32),
            jnp.asarray(rng.random((n, T)), jnp.float32),
            jnp.asarray(rng.random((n, C)), jnp.float32))
    text = jax_ops._sharded_product.lower(
        *args, mesh=mesh, total_leaves=L, data_axis="data",
        model_axis="model").compile().as_text()
    paths = [p.split("/") for p in _op_names(text)]
    assert any("bucket" in p for p in paths)
    assert any("gather" in p for p in paths)
