"""``chip_smoke.py`` at a tiny size on the CPU: every one-chip phase with the
Pallas kernels in interpret mode, its checks, and its refusal to run
without a TPU."""
import importlib.util
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_at_tiny_size(chip_smoke, monkeypatch):
    from repro.forest import training
    # the trainer's Pallas kernels (interpret mode on the CPU), not the
    # XLA reference it uses on the CPU by default
    monkeypatch.setattr(training, "_JAX_USE_PALLAS", True)
    rep = chip_smoke.Report()
    try:
        chip_smoke.run_one_chip(
            chip_smoke.Config(n_train=400, n_test=100, n_trees=4), rep)
    finally:
        rep.close()
    assert rep.failures == []
    paths = chip_smoke.op_paths()
    for backend in ("pallas", "jax"):
        for op in ("matmat", "kernel_block", "topk", "squared_row_sums"):
            assert paths.get((op, backend, "device"), 0) > 0, (op, backend)


def test_check_records_a_disagreement(chip_smoke):
    rep = chip_smoke.Report()
    try:
        want = np.linspace(1.0, 2.0, 10)
        rep.check("same", want + 1e-12, want, "float64")
        assert rep.failures == []
        rep.check("float32 where float64 was asked",
                  want.astype(np.float32) + 1e-6, want, "float64")
        rep.check("shape", want[:5], want, "float32")
    finally:
        rep.close()
    assert len(rep.failures) == 2


def _internal_nodes(tree, Xb, rows, edges):
    """(node, depth, sample rows) of every split node, level by level."""
    out, level, depth = [], [(0, rows)], 0
    while level:
        nxt = []
        for k, r in level:
            f = tree.feature[k]
            if f >= 0:
                out.append((k, depth, r))
                left = Xb[r, f] <= np.flatnonzero(
                    edges[f] == tree.threshold[k])[0]
                nxt += [(tree.left[k], r[left]), (tree.right[k], r[~left])]
        level, depth = nxt, depth + 1
    return out


def _resplit(tree, changes):
    """``tree`` with node k split at (feature, threshold) per ``changes``."""
    import dataclasses
    feat, thr = tree.feature.copy(), tree.threshold.copy()
    for k, (f, t) in changes.items():
        feat[k], thr[k] = f, t
    return dataclasses.replace(tree, feature=feat, threshold=thr)


@pytest.mark.parametrize("case, fails", [
    ("worse root split", True),
    ("other field differs", True),
    ("tie, then a worse split deeper", False),
    ("tie, and a worse split on the same level", True),
])
def test_tree_diffs_are_explained_or_fail(chip_smoke, case, fails):
    """A device tree must first part from the native one at tied splits
    only; partings below that level follow from shifted feature draws and
    are not scored."""
    import dataclasses
    from repro.core.api import ForestKernel
    cfg = chip_smoke.Config(n_train=300, n_test=20, n_trees=2)
    Xtr, ytr, _, _ = chip_smoke.make_data(cfg)
    forest = ForestKernel(n_trees=2, tree_backend="native").fit_forest(
        Xtr, ytr).forest
    tree, binner = forest.trees_[0], forest.binner_
    d, B = Xtr.shape[1], int(binner.n_bins)
    edges = binner.thresholds(np.repeat(np.arange(d), B),
                              np.tile(np.arange(B), d)
                              ).astype(np.float32).reshape(d, B)
    Xb = binner.transform(Xtr)
    nodes = _internal_nodes(tree, Xb, np.flatnonzero(forest.inbag_[0]),
                            edges)
    worse = lambda k: ((int(tree.feature[k]) + 1) % d,
                       edges[(int(tree.feature[k]) + 1) % d, 0])
    if case == "worse root split":
        dev = _resplit(tree, {0: worse(0)})
    elif case == "other field differs":
        dev = dataclasses.replace(tree, value=tree.value + 1)
    else:
        # the shallowest node whose split ties with the next bin's: no
        # sample of the node falls in that bin, so both split it alike
        k, depth, rows = next(
            (k, dep, r) for k, dep, r in nodes
            if (c := np.flatnonzero(edges[tree.feature[k]]
                                    == tree.threshold[k])[0]) < B - 2
            and not (Xb[r, tree.feature[k]] == c + 1).any()
            and any(dep2 == dep and k2 != k for k2, dep2, _ in nodes)
            and any(dep2 > dep and not np.isin(r2, r).all()
                    for _, dep2, r2 in nodes))
        c = np.flatnonzero(edges[tree.feature[k]] == tree.threshold[k])[0]
        tie = {k: (tree.feature[k], edges[tree.feature[k], c + 1])}
        # a node on the same level, or deeper and outside k's subtree
        other = next(k2 for k2, dep2, r2 in nodes
                     if (dep2 == depth and k2 != k
                         if case.endswith("same level") else
                         dep2 > depth and not np.isin(r2, rows).all()))
        dev = _resplit(tree, {**tie, other: worse(other)})
    rep = chip_smoke.Report()
    try:
        chip_smoke.explain_tree_diffs(forest, [(0, dev, tree)], Xtr, ytr, rep)
    finally:
        rep.close()
    assert bool(rep.failures) == fails, rep.failures


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and the code sets no other
    directory; without it the cache sits at the fixed in-checkout path."""
    import jax
    from repro.core.compile_cache import (CHECKOUT_CACHE_DIR,
                                          configure_compile_cache)
    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = configure_compile_cache()
        assert got == (env or str(CHECKOUT_CACHE_DIR))
        assert jax.config.jax_compilation_cache_dir == (
            old["jax_compilation_cache_dir"] if env else got)
        # JAX's threshold for what is worth writing stays its own
        assert jax.config.jax_persistent_cache_min_compile_time_secs == \
            old["jax_persistent_cache_min_compile_time_secs"]
        assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    finally:
        for k, v in old.items():
            jax.config.update(k, v)


def test_refuses_a_cpu_only_backend(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
