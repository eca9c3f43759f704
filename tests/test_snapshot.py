"""Durable snapshot round-trips (ISSUE-7 tentpole acceptance).

``save`` → ``load`` must reproduce the original engine exactly — every op
agrees at 1e-8 on every available backend — without refitting (the saved
weight factors are injected, skipping the assignment's weight computation).
Tampered archives, wrong versions, and foreign npz files are rejected with
:class:`SnapshotError`.
"""
import json

import numpy as np
import pytest

from repro.core.api import ForestKernel
from repro.core.engine import ENGINE_BACKENDS
from repro.core.snapshot import (SNAPSHOT_VERSION, SnapshotError,
                                 load_kernel, save_kernel)
from repro.data.synthetic import gaussian_classes
from repro.forest import _native

from _hyp import given, settings, st

BACKENDS = [be for be in ENGINE_BACKENDS
            if be != "native" or _native.available()]


@pytest.fixture(scope="module")
def snap_setup(tmp_path_factory):
    X, y = gaussian_classes(400, d=8, n_classes=3, sep=3.0, seed=11)
    fk = ForestKernel(kernel_method="gap", n_trees=12, seed=0).fit(X, y)
    path = tmp_path_factory.mktemp("snap") / "kernel.npz"
    manifest = save_kernel(fk, path)
    Xq = np.ascontiguousarray(X[:32] + 1e-3)
    return {"fk": fk, "path": path, "manifest": manifest,
            "X": X, "y": y, "Xq": Xq}


def _tamper(src, dst, mutate):
    """Re-save ``src`` with ``mutate(arrays)`` applied (manifest included),
    preserving the zip-level integrity so only *our* validation can object."""
    with np.load(src) as data:
        arrays = {k: data[k] for k in data.files}
    mutate(arrays)
    np.savez(dst, **arrays)
    return dst


def _edit_manifest(arrays, **updates):
    manifest = json.loads(bytes(arrays["manifest"].tobytes()).decode())
    manifest.update(updates)
    arrays["manifest"] = np.frombuffer(
        json.dumps(manifest).encode(), dtype=np.uint8)


# ---------------------------------------------------------------------------
# round-trip conformance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_roundtrip_all_ops_conformant(snap_setup, backend):
    fk, Xq, y = snap_setup["fk"], snap_setup["Xq"], snap_setup["y"]
    fk2 = ForestKernel.load(snap_setup["path"], engine_backend=backend)

    assert fk2.engine.backend == backend
    np.testing.assert_allclose(np.asarray(fk2.kernel().todense()),
                               np.asarray(fk.kernel().todense()), atol=1e-8)
    np.testing.assert_allclose(
        fk2.engine.predict(y, n_classes=3, X=Xq),
        fk.engine.predict(y, n_classes=3, X=Xq), atol=1e-8)
    np.testing.assert_allclose(fk2.engine.row_sums(X=Xq),
                               fk.engine.row_sums(X=Xq), atol=1e-8)
    _, v1 = fk.engine.topk(k=5, X=Xq)
    _, v2 = fk2.engine.topk(k=5, X=Xq)
    np.testing.assert_allclose(v2, v1, atol=1e-8)
    rows, cols = np.arange(10), np.arange(25)
    np.testing.assert_allclose(fk2.engine.kernel_block(rows, cols),
                               fk.engine.kernel_block(rows, cols), atol=1e-8)
    # the rebuilt forest routes queries identically
    np.testing.assert_array_equal(fk2.forest.apply(Xq), fk.forest.apply(Xq))


def test_roundtrip_is_bit_identical(snap_setup):
    fk = snap_setup["fk"]
    fk2 = ForestKernel.load(snap_setup["path"])
    np.testing.assert_array_equal(fk2.engine.q, fk.engine.q)
    np.testing.assert_array_equal(fk2.engine.w, fk.engine.w)
    np.testing.assert_array_equal(fk2.ctx.leaves, fk.ctx.leaves)
    assert fk2.ctx.digest() == fk.ctx.digest()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_roundtrip_random_query_batches(snap_setup, seed):
    """Property: any OOS batch sees identical predictions pre/post reload."""
    fk, X, y = snap_setup["fk"], snap_setup["X"], snap_setup["y"]
    fk2 = ForestKernel.load(snap_setup["path"])
    rng = np.random.default_rng(seed)
    Xq = X[rng.integers(0, len(X), size=16)] + \
        rng.normal(scale=0.05, size=(16, X.shape[1]))
    Xq = np.ascontiguousarray(Xq)
    np.testing.assert_allclose(
        fk2.engine.predict(y, n_classes=3, X=Xq),
        fk.engine.predict(y, n_classes=3, X=Xq), atol=1e-8)


def test_warm_start_skips_weight_recompute(tmp_path, monkeypatch):
    """The point of warm-starting: loading must not re-run the assignment's
    (possibly expensive) weight computation — factors come from the file."""
    from repro.core import weights as W

    X, y = gaussian_classes(300, d=6, n_classes=2, sep=3.0, seed=3)
    fk = ForestKernel(kernel_method="ih", n_trees=8, seed=0).fit(X, y)
    p = tmp_path / "ih.npz"
    fk.save(p)

    def boom(self, *a, **kw):
        raise AssertionError("reference_weights recomputed on load")

    monkeypatch.setattr(W.InstanceHardness, "reference_weights", boom)
    fk2 = ForestKernel.load(p)
    np.testing.assert_allclose(np.asarray(fk2.kernel().todense()),
                               np.asarray(fk.kernel().todense()), atol=1e-8)


def test_gbt_snapshot_restores_learning_rate(tmp_path):
    X, y = gaussian_classes(300, d=6, n_classes=2, sep=3.0, seed=9)
    fk = ForestKernel(model_type="gbt", kernel_method="boosted", n_trees=8,
                      learning_rate=0.3, seed=0).fit(X, y)
    p = tmp_path / "gbt.npz"
    fk.save(p)
    fk2 = ForestKernel.load(p)
    assert fk2.learning_rate == fk2.forest.learning_rate == 0.3
    Xq = np.ascontiguousarray(X[:20] + 1e-3)
    np.testing.assert_allclose(fk2.forest.decision_function(Xq),
                               fk.forest.decision_function(Xq), atol=1e-12)


def test_gbt_snapshot_restores_base_score(tmp_path):
    X, y = gaussian_classes(300, d=6, n_classes=2, sep=3.0, seed=9)
    fk = ForestKernel(model_type="gbt", kernel_method="boosted",
                      n_trees=8, seed=0).fit(X, y)
    p = tmp_path / "gbt.npz"
    fk.save(p)
    fk2 = ForestKernel.load(p)
    assert fk2.forest.base_score_ == pytest.approx(fk.forest.base_score_)
    Xq = np.ascontiguousarray(X[:20] + 1e-3)
    np.testing.assert_allclose(fk2.forest.predict(Xq), fk.forest.predict(Xq),
                               atol=1e-8)


# ---------------------------------------------------------------------------
# rejection paths
# ---------------------------------------------------------------------------

def test_corrupted_array_rejected(snap_setup, tmp_path):
    def flip(arrays):
        a = arrays["factor_q_data"].copy()
        a.flat[0] += 1.0
        arrays["factor_q_data"] = a

    bad = _tamper(snap_setup["path"], tmp_path / "bad.npz", flip)
    with pytest.raises(SnapshotError, match="checksum mismatch"):
        load_kernel(bad)


def test_missing_array_rejected(snap_setup, tmp_path):
    bad = _tamper(snap_setup["path"], tmp_path / "missing.npz",
                  lambda arrays: arrays.pop("factor_q_data"))
    with pytest.raises(SnapshotError, match="missing array"):
        load_kernel(bad)


def test_version_mismatch_rejected(snap_setup, tmp_path):
    bad = _tamper(snap_setup["path"], tmp_path / "ver.npz",
                  lambda a: _edit_manifest(a, version=SNAPSHOT_VERSION + 1))
    with pytest.raises(SnapshotError, match="version"):
        load_kernel(bad)


def test_foreign_format_rejected(snap_setup, tmp_path):
    bad = _tamper(snap_setup["path"], tmp_path / "fmt.npz",
                  lambda a: _edit_manifest(a, format="something-else"))
    with pytest.raises(SnapshotError, match="format"):
        load_kernel(bad)

    plain = tmp_path / "plain.npz"
    np.savez(plain, a=np.arange(3))
    with pytest.raises(SnapshotError, match="manifest"):
        load_kernel(plain)


def test_unfitted_kernel_refuses_to_save(tmp_path):
    fk = ForestKernel(n_trees=4)
    with pytest.raises(ValueError, match="fit"):
        fk.save(tmp_path / "nope.npz")
