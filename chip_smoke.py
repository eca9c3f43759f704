#!/usr/bin/env python3
"""Smoke run of the SWLC main path on a TPU: fit -> factorize -> engine ops
-> tiered serving, through the entry points a user calls (``ForestKernel``,
``ProximityEngine``, ``TieredProximityServer``).

Deployment: the serving deployment of ``benchmarks/bench_serving_prox.py`` —
``gaussian_classes(52_000, d=20, n_classes=4, seed=0)`` split into 50,000
training rows and 2,000 out-of-sample (OOS) query rows, and
``ForestKernel(kernel_method="gap", n_trees=50, seed=0)`` (64 bins, depth
cap 64).

  python chip_smoke.py              # one chip: every phase below
  python chip_smoke.py --chips 4    # only the sharded training-set matmat
                                    # on all four chips vs one chip

One-chip phases, each checked against a host reference:

  fit        ``tree_backend="jax"``: Pallas histogram kernels and split
             scoring in float64 (x64) on the device; the trees must equal
             ``tree_backend="native"``'s field for field.
  factorize  engines on ``pallas`` (device) and ``scipy`` (the float64
             reference) over the same forest.
  engine     predict / row sums / kernel block / top-k / squared row sums,
             training-set and OOS, against the scipy engine.
  serve      ``ForestKernel.serve_tiered`` answers predict, topk, outlier,
             propagate and embed requests on OOS rows; each answer is
             compared with the scipy engine of the tier that gave it.

OOS routing runs on the host (``routing_backend="auto"``).  Every line
before the last reports progress: per-phase seconds with compile time
counted apart, where each engine op computed (device, host, or the
training-set host cutover), the dtype each device op computed in, and
whether the Pallas kernels ran compiled.  The script exits non-zero if a
phase fails or no TPU is present; on success the last line of stdout is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core.api import ForestKernel  # noqa: E402
from repro.data.synthetic import gaussian_classes, train_test_split  # noqa: E402

TREE_FIELDS = ("feature", "threshold", "left", "right", "leaf_id", "value",
               "n_node_samples")

# Largest |device - reference| over the largest |reference| of each compared
# array, by the dtype the device op computed in:
#   float64 — the same float64 sums in another order: ~T·eps64 ≈ 1e-14 of
#             the scale; 1e-9 leaves room for the TPU's emulated float64 and
#             still fails an op that silently computed in float32 (~1e-7);
#   float32 — inputs rounded to float32 and T = 50 tree terms summed in
#             float32: ≤ ~T·eps32 ≈ 6e-6 of the scale.  A dropped tree term
#             moves a value by ~1/T = 2e-2, far above the limit.
TOL = {"float64": 1e-9, "float32": 2e-5}


@dataclasses.dataclass(frozen=True)
class Config:
    n_train: int = 50_000
    n_test: int = 2_000
    d: int = 20
    n_classes: int = 4
    n_trees: int = 50
    seed: int = 0
    prefix_depth: int = 4
    n_prototypes: int = 10
    proto_k: int = 50
    k: int = 10             # top-k width
    rows: int = 16          # OOS rows per served request / engine-op batch


class Report:
    """Progress lines, compile-time accounting and failed checks."""

    def __init__(self):
        import jax
        self.failures: list = []
        self._compile = [0.0, 0]
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile[0] += secs
            self._compile[1] += 1

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        self.log(f"== {name}")
        t0, (c0, n0) = time.perf_counter(), self._compile
        yield
        wall = time.perf_counter() - t0
        self.log(f"   {name}: {wall:.3f} s wall, of which "
                 f"{self._compile[0] - c0:.3f} s compiling "
                 f"({self._compile[1] - n0} programs)")

    def check(self, name: str, got, want, dtype: str) -> None:
        got = np.asarray(got, dtype=np.float64)
        want = np.asarray(want, dtype=np.float64)
        tol = TOL[dtype]
        if got.shape != want.shape:
            self._fail(f"{name}: shape {got.shape} != reference {want.shape}")
            return
        if not np.isfinite(got).all():
            self._fail(f"{name}: non-finite values")
            return
        scale = max(float(np.abs(want).max(initial=0.0)),
                    np.finfo(np.float64).tiny)
        err = float(np.abs(got - want).max(initial=0.0)) / scale
        ok = err <= tol
        self.log(f"   {name}: rel err {err:.3e} (limit {tol:.0e}, {dtype}) "
                 f"{'ok' if ok else 'FAIL'}")
        if not ok:
            self.failures.append(f"{name}: rel err {err:.3e} > {tol:.0e}")

    def _fail(self, msg: str) -> None:
        self.log(f"   FAIL {msg}")
        self.failures.append(msg)


def make_data(cfg: Config):
    n = cfg.n_train + cfg.n_test
    X, y = gaussian_classes(n, d=cfg.d, n_classes=cfg.n_classes,
                            seed=cfg.seed)
    return train_test_split(X, y, test_frac=cfg.n_test / n, seed=cfg.seed)


def kernel_on(fk: ForestKernel, backend: str) -> ForestKernel:
    """``fk``'s configuration and fitted forest with another engine."""
    kw = fk._config_kwargs()
    kw["engine_backend"] = backend
    other = ForestKernel(**kw)
    other.forest = fk.forest
    return other.build_kernel_cache()


def op_paths() -> dict:
    """{(op, backend, path): count} of the engine_op_path_total family."""
    from repro.obs.metrics import global_registry
    fam = global_registry().snapshot().get("engine_op_path_total", {})
    return {tuple(kv.split("=", 1)[1] for kv in key.split(",")): n
            for key, n in fam.get("series", {}).items()}


def phase_fit(cfg: Config, Xtr, ytr, rep: Report) -> ForestKernel:
    import jax
    fk = ForestKernel(kernel_method="gap", n_trees=cfg.n_trees,
                      seed=cfg.seed, tree_backend="jax",
                      engine_backend="pallas")
    with rep.phase("fit (tree_backend=jax)"):
        # x64 so splits are scored in float64, as the native trainer does
        with jax.enable_x64(True):
            fk.fit_forest(Xtr, ytr)
    with rep.phase("fit reference (tree_backend=native)"):
        nat = ForestKernel(kernel_method="gap", n_trees=cfg.n_trees,
                           seed=cfg.seed, tree_backend="native"
                           ).fit_forest(Xtr, ytr)
    dev, ref = fk.forest.trees_, nat.forest.trees_
    differ = [i for i, (a, b) in enumerate(zip(dev, ref))
              if any(getattr(a, f).dtype != getattr(b, f).dtype
                     or not np.array_equal(getattr(a, f), getattr(b, f))
                     for f in TREE_FIELDS)]
    rep.log(f"   fit: {len(ref) - len(differ)} of {len(ref)} trees "
            f"identical to the native trainer field for field")
    if len(dev) != len(ref):
        rep._fail(f"fit: {len(dev)} trees, native {len(ref)}")
    elif differ:
        explain_tree_diffs(nat.forest, [(i, dev[i], ref[i]) for i in differ],
                           Xtr, ytr, rep)
    f64_probe(rep)
    return fk


# A split the device chose instead of the native trainer's must have a
# numpy float64 gain within this fraction of the node's parent term
# Σ_c n_c² / n.  The device's emulated float64 division is not IEEE-exact
# (``f64_probe`` measures it at ~1e-14 relative), which is what breaks
# exact and one-ulp ties the other way; splits that are not tied differ by
# orders of magnitude more.
TIE_TOL = 1e-11


def explain_tree_diffs(forest, pairs, Xtr, ytr, rep: Report) -> None:
    """Walk each differing (device, native) tree pair level by level from
    the root.  At the shallowest level where their splits part ways, every
    node above is identical, so the parting nodes hold the same samples and
    drew the same random feature subset: score each one's histogram in
    numpy float64, and the two choices must tie (within ``TIE_TOL``) — the
    emulated-float64 cause.  Deeper partings follow from it (the trainer
    draws each level's feature subsets in frontier order, so a changed
    frontier shifts every later draw of that tree) and are only counted.
    A non-tie, or trees whose splits agree everywhere yet differ, fails
    the fit."""
    from repro.forest.training import split_gains
    binner = forest.binner_
    Xb = binner.transform(Xtr).astype(np.int64)
    y = np.asarray(ytr, dtype=np.int64)
    d, B, C = Xtr.shape[1], int(binner.n_bins), int(forest.n_classes_)
    edges = binner.thresholds(np.repeat(np.arange(d), B),
                              np.tile(np.arange(B), d)
                              ).astype(np.float32).reshape(d, B)

    def code(tree, k):          # the bin code a node splits at
        return int(np.flatnonzero(edges[tree.feature[k]]
                                  == tree.threshold[k])[0])

    worst, n_first = 0.0, 0
    for t, a, b in pairs:
        w_all = forest.inbag_[t]
        level = [(0, 0, np.flatnonzero(w_all))]
        parted = []
        while level and not parted:
            nxt = []
            for i, j, rows in level:
                fa = a.feature[i]
                if fa != b.feature[j] or (fa >= 0 and
                                          a.threshold[i] != b.threshold[j]):
                    parted.append((i, j, rows))
                elif fa >= 0:
                    left = Xb[rows, fa] <= code(a, i)
                    nxt += [(a.left[i], b.left[j], rows[left]),
                            (a.right[i], b.right[j], rows[~left])]
            level = nxt
        if not parted:
            rep._fail(f"fit: tree {t} has the native splits but other "
                      f"fields differ")
            return
        for i, j, rows in parted:
            hist = np.stack([np.bincount(
                Xb[rows, f] * C + y[rows], weights=w_all[rows],
                minlength=B * C).reshape(B, C) for f in range(d)])
            gain, tot = split_gains(hist[None].astype(np.float64), 1.0, True)
            g = lambda tree, k: 0.0 if tree.feature[k] < 0 else \
                float(gain[0, tree.feature[k], code(tree, k)])
            parent = float((tot[0] ** 2).sum() / tot[0].sum())
            gap = abs(g(a, i) - g(b, j)) / parent
            worst = max(worst, gap)
            rep.log(f"   tree {t} node {i}: device splits feature "
                    f"{a.feature[i]}, native feature {b.feature[j]}; "
                    f"float64 gains {g(a, i)!r} vs {g(b, j)!r} "
                    f"(gap {gap:.3e} of the parent term)")
        n_first += len(parted)
    ok = worst <= TIE_TOL
    rep.log(f"   fit: {len(pairs)} trees first part from the native ones at "
            f"{n_first} nodes, each a split the native trainer scores within "
            f"{worst:.3e} of its own (limit {TIE_TOL:.0e}): ties broken by "
            f"the device's emulated float64 {'ok' if ok else 'FAIL'}")
    if not ok:
        rep.failures.append(f"fit: a device split is {worst:.3e} worse "
                            f"than the native one (limit {TIE_TOL:.0e})")


def f64_probe(rep: Report) -> None:
    """How the device's float64 division compares with IEEE float64."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    a = rng.integers(1, 2 ** 31, 100_000).astype(np.float64)
    b = rng.integers(1, 2 ** 16, 100_000).astype(np.float64)
    with jax.enable_x64(True):
        got = np.asarray(jax.jit(jnp.divide)(a, b))
    want = a / b
    rep.log(f"   device float64 a/b on integers: "
            f"{np.mean(got != want):.3%} not IEEE-exact, max rel err "
            f"{np.max(np.abs(got - want) / want):.3e}")


def phase_factorize(fk: ForestKernel, rep: Report) -> dict:
    kernels = {}
    with rep.phase("factorize (pallas engine)"):
        kernels["pallas"] = fk.build_kernel_cache()
    with rep.phase("factorize (scipy engine)"):
        kernels["scipy"] = kernel_on(fk, "scipy")
    eng = kernels["pallas"].engine
    rep.log(f"   factors: {eng.gl.shape[0]} rows x {eng.gl.shape[1]} trees, "
            f"{eng.total_leaves} leaves")
    return kernels


def phase_engine(cfg: Config, kernels: dict, Xte, rep: Report) -> None:
    ref = kernels["scipy"].engine
    y = kernels["scipy"].ctx.y
    C = cfg.n_classes
    Xq = Xte[:cfg.rows]
    backend = "pallas"
    eng = kernels[backend].engine
    dt, bdt = eng.dtype.name, eng.block_dtype.name
    with rep.phase(f"engine ops ({backend})"):
        rep.check(f"{backend} predict train", eng.predict(y, C),
                  ref.predict(y, C), dt)
        rep.check(f"{backend} predict oos", eng.predict(y, C, X=Xte),
                  ref.predict(y, C, X=Xte), dt)
        rep.check(f"{backend} row_sums train", eng.row_sums(),
                  ref.row_sums(), dt)
        want_block = ref.kernel_block(X_rows=Xq)
        rep.check(f"{backend} kernel_block oos",
                  eng.kernel_block(X_rows=Xq), want_block, bdt)
        idx, val = eng.topk(cfg.k, X=Xq)
        want_idx, want_val = ref.topk(cfg.k, X=Xq)
        rep.check(f"{backend} topk oos values", val, want_val, bdt)
        rep.check(f"{backend} topk oos P[i, index]", val,
                  np.take_along_axis(want_block, idx, axis=1), bdt)
        rep.check(f"{backend} squared_row_sums oos",
                  eng.squared_row_sums(class_ids=y, n_classes=C, X=Xq),
                  ref.squared_row_sums(class_ids=y, n_classes=C, X=Xq),
                  bdt)
    rep.log(f"   {backend} dtypes: matmat/predict/row_sums {dt}; "
            f"kernel_block/topk/squared_row_sums {bdt}")


def _tier_refs(srv, ref, cfg: Config) -> dict:
    """(scipy engine, labels, tier) per tier name of ``srv``, the reference
    engine being ``ref`` cut the way the tier's engine is."""
    from repro.applications.prototypes import CompressedProximityEngine
    from repro.core.engine import PrefixProximityEngine
    out = {}
    for tier in srv.tiers:
        if tier.name == "shallow":
            eng = PrefixProximityEngine(ref, cfg.prefix_depth)
        elif tier.name == "compressed":
            ce = tier.engine
            eng = CompressedProximityEngine(
                ref, ce.prototype_indices_, labels=ce.prototype_labels_)
        else:
            eng = ref
        out[tier.name] = (eng, tier.y, tier)
    return out


def phase_serve(cfg: Config, kernels: dict, ytr, Xte, rep: Report) -> None:
    from repro.applications.embed import ProximityEmbedding
    from repro.applications.outliers import (oos_outlier_scores,
                                             train_outlier_stats)
    ref_kernel = kernels["scipy"]
    labeled = np.random.default_rng(cfg.seed).random(len(ytr)) < 0.1
    C = cfg.n_classes
    backend = "pallas"
    k = kernels[backend]
    eng = k.engine
    with rep.phase(f"serve setup ({backend}): propagation, embedding, "
                   f"compression"):
        prop = k.propagate_labels(labeled, online=True)
        emb = ProximityEmbedding(n_components=2, seed=cfg.seed).fit(eng)
        ce = k.compress(n_prototypes=cfg.n_prototypes, k=cfg.proto_k)
        srv = k.serve_tiered(prefix_depth=cfg.prefix_depth,
                             compressed_engine=ce, n_slots=64,
                             escalate_margin=0.3, propagator=prop,
                             embedding=emb)
    r = cfg.rows
    reqs = [("predict", Xte[0:r]), ("predict", Xte[r:2 * r]),
            ("topk", Xte[2 * r:3 * r], cfg.k), ("outlier", Xte[3 * r:4 * r]),
            ("propagate", Xte[4 * r:5 * r]), ("embed", Xte[5 * r:6 * r])]
    with rep.phase(f"serve ({backend}): {len(reqs)} requests"):
        uids = [srv.submit(*q) for q in reqs]
        srv.run_until_drained()
    done = {t.uid: t for t in srv.finished}
    refs = _tier_refs(srv, ref_kernel.engine, cfg)
    for uid, q in zip(uids, reqs):
        kind, Xr = q[0], q[1]
        t = done.get(uid)
        if t is None or t.result is None:
            rep._fail(f"serve {backend} {kind}: no answer "
                      f"(shed={getattr(t, 'shed', None)}, "
                      f"failed={getattr(t, 'failed', None)})")
            continue
        tier = t.final_tier
        reng, ty, tobj = refs[tier]
        teng = tobj.engine
        name = (f"{backend} serve {kind} via {'->'.join(t.tier_path)} "
                f"(answered by {tier})")
        res = t.result
        if kind == "predict":
            rep.check(name, res["scores"],
                      reng.predict(ty, n_classes=C, X=Xr),
                      teng.dtype.name)
        elif kind == "topk":
            bdt = teng.block_dtype.name
            full = ref_kernel.engine.kernel_block(X_rows=Xr)
            idx, val = res["indices"], res["values"]
            at = np.where(idx >= 0, np.take_along_axis(
                full, np.maximum(idx, 0), axis=1), 0.0)
            rep.check(name + " P[i, index]", val, at, bdt)
            want = reng.topk(k=q[2], X=Xr)[1]
            rep.check(name + " values", val, want, bdt)
        elif kind == "outlier":
            stats = train_outlier_stats(reng, ty, n_classes=C)
            want, cls = oos_outlier_scores(reng, ty, Xr, n_classes=C,
                                           return_classes=True)
            # compare raw n_c / Σ P² (what the device computed) — the
            # median/MAD normalization would rescale the error
            mad, med = stats["mad"][cls], stats["median"][cls]
            rep.check(name + " raw", res["scores"] * mad + med,
                      want * mad + med, teng.block_dtype.name)
        elif kind == "propagate":
            # the served field's OOS projection, rows scaled to sum 1
            Fb = reng.matmat(prop.F, X=Xr, normalized=True)
            rep.check(name, res["scores"],
                      Fb / np.maximum(Fb.sum(axis=1, keepdims=True),
                                      np.finfo(np.float64).tiny),
                      teng.dtype.name)
        else:
            rep.check(name, res["embedding"],
                      reng.matmat(emb._nystrom, X=Xr), teng.dtype.name)
    st = srv.stats()
    rep.log(f"   {backend} serving: {st['requests']} requests, "
            f"escalations {st['escalations']}, shed {st['shed']}, "
            f"timeouts {st['timeouts']}")


def report_paths(rep: Report) -> None:
    rep.log("== where engine ops computed (op, backend, path: calls)")
    for (op, backend, path), n in sorted(op_paths().items()):
        rep.log(f"   {op:>16} {backend:>6} {path:>12}: {int(n)}")


def report_kernels(rep: Report) -> None:
    from repro.kernels import interpret_mode
    mode = "interpret mode" if interpret_mode() else "compiled"
    rep.log(f"== Pallas kernels: {mode}")
    rep.log(f"   histogram_pallas (fit, float32 histograms): {mode}")
    rep.log(f"   block_prox (pallas kernel_block/topk/squared_row_sums, "
            f"float32): {mode}")
    rep.log("   leaf_route: not run (OOS routing on the host, "
            "routing_backend=auto)")


def run_one_chip(cfg: Config, rep: Report) -> None:
    Xtr, ytr, Xte, _ = make_data(cfg)
    rep.log(f"data: {len(Xtr)} training rows, {len(Xte)} OOS rows, "
            f"d={cfg.d}, {cfg.n_classes} classes")
    fk = phase_fit(cfg, Xtr, ytr, rep)
    kernels = phase_factorize(fk, rep)
    phase_engine(cfg, kernels, Xte, rep)
    phase_serve(cfg, kernels, ytr, Xte, rep)
    report_paths(rep)
    report_kernels(rep)


def run_sharded(cfg: Config, rep: Report) -> None:
    """The engine's training-set matmat over every visible device (the
    sharded path any multi-chip host takes) vs one device and scipy."""
    import jax
    import jax.numpy as jnp
    from repro.core import jax_ops
    Xtr, ytr, _, _ = make_data(cfg)
    with rep.phase("fit (tree_backend=native, host)"):
        fk = ForestKernel(kernel_method="gap", n_trees=cfg.n_trees,
                          seed=cfg.seed, tree_backend="native",
                          engine_backend="pallas").fit(Xtr, ytr)
    ref = kernel_on(fk, "scipy").engine
    eng = fk.engine
    C = cfg.n_classes
    V = np.random.default_rng(cfg.seed).normal(size=(len(Xtr), C))
    with rep.phase(f"sharded matmat ({len(jax.devices())} devices)"):
        got = eng.matmat(V)
        pred = eng.predict(ytr, C)
    if eng.last_matmat_path != "sharded":
        rep._fail(f"matmat took the {eng.last_matmat_path!r} path, "
                  f"not 'sharded'")
    with rep.phase("one-device segment matmat"):
        with jax.enable_x64(True):
            gl, w = jnp.asarray(eng.gl), jnp.asarray(eng.w)
            one = np.asarray(jax_ops._swlc_product(
                gl, jnp.asarray(eng.q), gl, w, jnp.asarray(V),
                eng.total_leaves, t_chunk=1))
    dt = eng.dtype.name
    rep.check("sharded matmat vs one device", got, one, dt)
    rep.check("sharded matmat vs scipy", got, ref.matmat(V), dt)
    rep.check("sharded predict vs scipy", pred, ref.predict(ytr, C), dt)


def _require_tpu(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r} devices only")
    if len(devs) != chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} TPU devices")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded matmat across four chips")
    args = ap.parse_args(argv)
    device = _require_tpu(args.chips)
    from repro.core.compile_cache import configure_compile_cache
    rep = Report()
    rep.log(f"device: {device}; compile cache: {configure_compile_cache()}")
    t0 = time.perf_counter()
    try:
        (run_sharded if args.chips == 4 else run_one_chip)(Config(), rep)
    finally:
        rep.close()
    rep.log(f"total: {time.perf_counter() - t0:.3f} s")
    if rep.failures:
        rep.log("FAILED:\n  " + "\n  ".join(rep.failures))
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
