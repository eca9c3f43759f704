"""Proximity-serving benchmark: full vs prototype-compressed engine.

  PYTHONPATH=src:. python -m benchmarks.bench_serving_prox
      [--n 50000] [--trees 50] [--backend auto] [--out BENCH_serving_prox.json]

Fits one forest at ``--n`` training samples, builds (a) the full
``ProximityEngine`` and (b) its prototype-compressed counterpart
(``applications.prototypes.compress``), then drives identical mixed request
streams (predict / topk / outlier) through a ``ProximityServer`` on each and
reports per-request latency percentiles, throughput, factor memory, and the
accuracy cost of compression (OOS predict accuracy + agreement with the full
engine).  The headline acceptance: compressed serving must beat the full
engine on both p50 latency and factor memory at 50k training samples.
"""
from __future__ import annotations

import argparse
import gc
import math
import json
import time

import numpy as np

from repro.core.compile_cache import configure_compile_cache
from repro.applications.prototypes import compress
from repro.core.api import ForestKernel
from repro.data.synthetic import gaussian_classes, train_test_split
from repro.forest import _native
from repro.obs.metrics import MetricsRegistry, parse_exposition
from repro.obs.trace import Tracer
from repro.serve.proximity import ProximityServer
from repro.serve.reliability import FaultInjector, RetryPolicy


def _workload(Xte, n_requests: int, rows: int, seed: int = 0):
    """Deterministic mixed request stream over held-out rows."""
    rng = np.random.default_rng(seed)
    kinds = ["predict", "predict", "topk", "outlier"]   # 2:1:1 mix
    reqs = []
    for i in range(n_requests):
        kind = kinds[i % len(kinds)]
        sel = rng.integers(0, len(Xte), size=rows)
        if kind == "topk":
            reqs.append((kind, Xte[sel], 10))
        else:
            reqs.append((kind, Xte[sel]))
    return reqs


def _drive(server: ProximityServer, reqs, yte_for=None) -> dict:
    # warmup: build routed state / ref tables / train outlier stats once
    server.serve(reqs[:2])
    server.finished.clear()
    t0 = time.perf_counter()
    server.serve(reqs)
    wall = time.perf_counter() - t0
    st = server.stats()
    lat = [r.latency_s for r in server.finished]
    svc = [r.service_s for r in server.finished]
    rows = sum(r.n_rows for r in server.finished)
    out = {
        "requests": len(server.finished),
        "rows": rows,
        "wall_s": round(wall, 3),
        "rows_per_s": round(rows / wall, 1),
        "p50_ms": round(float(np.percentile(lat, 50) * 1e3), 3),
        "p95_ms": round(float(np.percentile(lat, 95) * 1e3), 3),
        "p50_service_ms": round(float(np.percentile(svc, 50) * 1e3), 3),
        "ticks": st["ticks"],
        "kinds": st["kinds"],
    }
    if yte_for is not None:
        Xte, yte = yte_for
        labels = server.serve([("predict", Xte)])[0]["labels"]
        out["oos_accuracy"] = round(float((labels == yte).mean()), 4)
        out["oos_labels"] = labels
    return out


def _sustained(fk, ce, Xte, ytr, *, slo_ms: float = 500.0, rows: int = 8,
               n_batches: int = 64, sync_requests: int = 10,
               ratio_target: float = 50.0, offered_factor: float = 1.25,
               max_requests: int = 1500, duration_s: float = 10.0,
               escalate_margin: float = 0.2, n_slots: int = 128,
               prefix_depth: int = 6, deadline_s: float = 4.0,
               assert_slo: bool = False, seed: int = 1) -> dict:
    """Sustained-throughput SLO mode: Poisson arrivals against the async
    tiered server (shallow → compressed → full) vs a synchronous full-engine
    baseline that serves one request at a time.

    Reports requests/s at the p95 latency SLO, deadline sheds at nominal
    load, and predict agreement vs the full engine (the escalation oracle).
    """
    rng = np.random.default_rng(seed)
    C = fk.forest.n_classes_
    pool = [np.ascontiguousarray(Xte[rng.integers(0, len(Xte), size=rows)])
            for _ in range(n_batches)]
    oracle = [fk.engine.predict(ytr, n_classes=C, X=b).argmax(1)
              for b in pool]
    kinds = ["predict", "predict", "topk", "outlier"]  # same mix as _drive

    def _req(i):
        kind = kinds[i % len(kinds)]
        bi = i % n_batches
        return (kind, pool[bi], 10) if kind == "topk" else (kind, pool[bi])

    # --- synchronous full-engine baseline: one request at a time ---------
    sync_srv = ProximityServer(fk.engine, y=ytr, n_slots=rows)
    sync_srv.serve([_req(i) for i in range(len(kinds))])  # warm every kind
    sync_srv.finished.clear()
    t0 = time.perf_counter()
    for i in range(sync_requests):
        sync_srv.serve([_req(i)])
    sync_wall = time.perf_counter() - t0
    sync_lat = [r.latency_s for r in sync_srv.finished]
    sync_rps = sync_requests / sync_wall
    out = {"slo_ms": slo_ms, "rows_per_request": rows,
           "sync_full": {
               "requests": sync_requests,
               "requests_per_s": round(sync_rps, 2),
               "p95_ms": round(float(np.percentile(sync_lat, 95) * 1e3), 2)}}

    # --- tiered async server under Poisson arrivals ----------------------
    offered_rps = ratio_target * sync_rps * offered_factor
    n_req = max(50, min(max_requests, int(offered_rps * duration_s)))
    srv = fk.serve_tiered(prefix_depth=prefix_depth, compressed_engine=ce,
                          n_slots=n_slots, escalate_margin=escalate_margin)
    srv.serve([_req(i) for i in range(len(kinds))])   # warm all tiers
    gaps = rng.exponential(1.0 / offered_rps, size=n_req)
    uid_batch = {}
    srv.start()
    try:
        t0 = time.perf_counter()
        next_at = t0
        for i in range(n_req):
            next_at += gaps[i]
            pause = next_at - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            kind, *rest = _req(i)
            uid = srv.submit(kind, rest[0], k=10, deadline_s=deadline_s)
            uid_batch[uid] = (kind, i % n_batches)
        srv.wait(list(uid_batch), timeout=120.0)
        wall = time.perf_counter() - t0
    finally:
        srv.stop()

    done = [r for r in srv.finished if r.uid in uid_batch]
    lat = [r.latency_s for r in done if r.latency_s is not None
           and not r.shed]
    preds = [r for r in done if uid_batch[r.uid][0] == "predict"
             and r.result is not None]
    agree = [float((r.result["labels"]
                    == oracle[uid_batch[r.uid][1]]).mean()) for r in preds]
    esc_agree = [float((r.result["labels"]
                        == oracle[uid_batch[r.uid][1]]).mean())
                 for r in preds if r.final_tier == "full"
                 and r.tier_path != ["full"]]
    st = srv.stats()
    p95 = float(np.percentile(lat, 95) * 1e3) if lat else float("inf")
    achieved = len(done) / wall
    out["tiered_async"] = {
        "requests": n_req,
        "offered_rps": round(offered_rps, 1),
        "achieved_rps": round(achieved, 1),
        "p50_ms": round(float(np.percentile(lat, 50) * 1e3), 2) if lat
        else None,
        "p95_ms": round(p95, 2),
        "shed": st["shed"], "timeouts": st["timeouts"],
        "escalations": st["escalations"],
        "escalation_rate": round(st["escalation_rate"], 4),
        "tier_requests": {name: t["routed_requests"]
                          for name, t in st["tiers"].items()},
    }
    out["speedup_vs_sync_full"] = round(achieved / sync_rps, 1)
    out["p95_slo_met"] = bool(p95 <= slo_ms)
    out["predict_agreement"] = round(float(np.mean(agree)), 4) if agree \
        else None
    out["escalated_oracle_agreement"] = round(float(np.mean(esc_agree)), 4) \
        if esc_agree else None
    # the run's full registry state rides along in the report, and the
    # exposition must round-trip through the strict parser
    exposition = srv.registry.exposition()
    out["exposition_series"] = len(parse_exposition(exposition))
    out["registry_snapshot"] = srv.registry.snapshot()
    print(f" sustained: sync full {sync_rps:.2f} req/s | tiered async "
          f"{achieved:.1f} req/s ({out['speedup_vs_sync_full']}x) "
          f"p95 {p95:.1f}ms (SLO {slo_ms}ms: "
          f"{'met' if out['p95_slo_met'] else 'MISSED'}) "
          f"shed={st['shed']} esc={st['escalations']} "
          f"agreement={out['predict_agreement']}", flush=True)
    if assert_slo:
        assert out["p95_slo_met"], \
            f"p95 {p95:.1f}ms exceeds the {slo_ms}ms SLO"
        assert st["shed"] == 0, f"{st['shed']} deadline sheds at nominal load"
        assert esc_agree and min(esc_agree) == 1.0, \
            "need >=1 escalated request whose labels match the full oracle"
    return out


def _obs_overhead(fk, ce, Xte, ytr, *, n_requests: int = 64, rows: int = 0,
                  n_slots: int = 256, reps: int = 10,
                  max_p95_inflation: float = 1.05,
                  assert_overhead: bool = False, seed: int = 3) -> dict:
    """Instrumentation-overhead mode: the identical mixed workload through
    a ``ProximityServer`` with observability ON (registry + tracer +
    engine timing proxy) and OFF (``MetricsRegistry(enabled=False)`` —
    engine calls skip the timing proxy, every metric is the shared no-op,
    no spans).

    Measurement design, tuned so a 5% bound is CI-stable on noisy shared
    machines (the instrumentation cost is a few tens of µs per request;
    naive wall-clock p95 comparisons drift by ±10% between runs):

    - Requests are served **one at a time** on both servers,
      **interleaved per request** with the serve order alternating, so
      each ON/OFF latency pair shares machine state (frequency scaling,
      cache pressure, sibling load) to within a few ms.
    - Requests are **slot-filling** (``rows`` defaults to ``n_slots``,
      sized independently of the SLO-mode config) so each carries one
      batch-scale engine tick of real work — the granularity the fixed
      per-request instrumentation cost should be judged against.
    - The server runs the **compressed engine** (the latency-critical
      serving model), giving a tight unimodal latency distribution; the
      tiered ladder's tail is multi-modal (escalation-path dependent),
      which swamps a 5% bound with routing noise.  Ladder span/metric
      coverage is exercised by the chaos and sustained modes and
      asserted by the trace tests.
    - The workload is replayed ``reps`` times and each request keeps its
      **fastest** replay per mode (the element-wise min strips scheduler
      jitter), giving a paired per-request inflation ratio that is
      drift-free by construction.  The asserted statistic is the
      **median ratio over the tail cluster** (requests whose baseline
      minimum sits in the top 15%) — the inflation experienced at the
      p95 latency point — with the raw p95s reported alongside.

    Acceptance: metrics + tracing may inflate tail latency by at most
    ``max_p95_inflation``x (5% by default).
    """
    rows = int(rows) if rows else n_slots
    reqs = _workload(Xte, n_requests, rows, seed=seed)

    def _build(instrumented: bool) -> ProximityServer:
        if instrumented:
            kw = {"registry": MetricsRegistry(enabled=True),
                  "tracer": Tracer(capacity=64)}
        else:
            kw = {"registry": MetricsRegistry(enabled=False),
                  "tracer": Tracer(enabled=False)}
        srv = ProximityServer(ce, y=ce.prototype_labels_, n_slots=n_slots,
                              **kw)
        srv.serve(reqs[:4])                    # warm every kind
        return srv

    def _one(srv: ProximityServer, r) -> float:
        srv.submit(*r)
        srv.run_until_drained()
        lat = srv.finished[-1].latency_s       # the request just served
        return lat if lat is not None else math.inf

    base = np.full(len(reqs), np.inf)
    instr = np.full(len(reqs), np.inf)
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()                   # GC pauses are ~100µs spikes — paired
    try:                           # runs must not eat them asymmetrically
        srv_off, srv_on = _build(False), _build(True)
        for rep in range(reps):
            for i, r in enumerate(reqs):
                if (rep + i) % 2 == 0:         # alternate order: the
                    b = _one(srv_off, r)       # second serve of the same
                    a = _one(srv_on, r)        # rows runs cache-warm
                else:
                    a = _one(srv_on, r)
                    b = _one(srv_off, r)
                if b < base[i]:
                    base[i] = b
                if a < instr[i]:
                    instr[i] = a
    finally:
        if gc_was_enabled:
            gc.enable()
    p95_off = float(np.percentile(base, 95) * 1e3)
    p95_on = float(np.percentile(instr, 95) * 1e3)
    ratios = instr / np.maximum(base, 1e-12)
    tail = base >= np.percentile(base, 85)
    inflation = float(np.median(ratios[tail]))
    out = {"reps": reps, "requests": n_requests, "rows": rows,
           "p95_ms_uninstrumented": round(p95_off, 3),
           "p95_ms_instrumented": round(p95_on, 3),
           "median_inflation": round(float(np.median(ratios)), 4),
           "tail_inflation": round(inflation, 4),
           "bound": max_p95_inflation,
           "within_bound": bool(inflation <= max_p95_inflation)}
    print(f" obs-overhead: p95 {p95_off:.2f}ms -> {p95_on:.2f}ms with "
          f"metrics+tracing on (tail inflation {inflation:.3f}x, bound "
          f"{max_p95_inflation}x: "
          f"{'met' if out['within_bound'] else 'EXCEEDED'})", flush=True)
    if assert_overhead:
        assert out["within_bound"], \
            f"observability inflates tail latency {inflation:.3f}x " \
            f"(bound {max_p95_inflation}x)"
    return out


def _chaos(fk, ce, Xte, ytr, *, error_rate: float = 0.15,
           corrupt_rate: float = 0.05, n_requests: int = 200, rows: int = 8,
           n_slots: int = 16, prefix_depth: int = 6,
           escalate_margin: float = 0.2, max_p95_inflation: float = 25.0,
           assert_chaos: bool = False, seed: int = 2) -> dict:
    """Chaos mode: the mixed workload against the tiered server with
    synthetic faults injected into >=5% of engine calls.

    The reliability contract under test: every admitted request either
    completes (possibly after retries / down-ladder re-routes) or is
    deterministically shed/failed with a recorded reason — zero silent
    losses — and p95 latency inflates by at most ``max_p95_inflation``x
    over the fault-free run.
    """
    reqs = _workload(Xte, n_requests, rows, seed=seed)

    def _drain(injector=None):
        srv = fk.serve_tiered(
            prefix_depth=prefix_depth, compressed_engine=ce,
            n_slots=n_slots, escalate_margin=escalate_margin,
            fault_injector=injector,
            retry=RetryPolicy(max_retries=2, backoff_s=0.001))
        srv.serve(reqs[:4])                      # warm every tier/kind
        t0 = time.perf_counter()
        uids = [srv.submit(*r) for r in reqs]
        srv.run_until_drained()
        wall = time.perf_counter() - t0
        lat = [srv._requests[u].latency_s for u in uids
               if srv._requests[u].latency_s is not None]
        return srv, uids, wall, float(np.percentile(lat, 95) * 1e3)

    _, _, clean_wall, clean_p95 = _drain(None)
    inj = FaultInjector(error_rate=error_rate, corrupt_rate=corrupt_rate,
                        seed=seed, sleep=lambda s: None)
    srv, uids, wall, p95 = _drain(inj)

    # --- zero-silent-loss accounting ------------------------------------
    lost = [u for u in uids if not srv._requests[u].done.is_set()]
    unaccounted = [u for u in uids
                   if srv._requests[u].result is None
                   and not (srv._requests[u].shed or srv._requests[u].failed
                            or srv._requests[u].timed_out)]
    st = srv.stats()
    rel = st["reliability"]
    identities_ok = all(
        s.faults == s.retries + s.failed_calls for s in srv._servers)
    ist = inj.stats()
    fault_rate = ist["injected"]["error"] / max(ist["calls"], 1)
    out = {
        "requests": len(uids),
        "injected": ist["injected"],
        "engine_calls": ist["calls"],
        "injected_fault_rate": round(fault_rate, 4),
        "faults": rel["faults"], "retries": rel["retries"],
        "recovered_calls": rel["recovered_calls"],
        "failed_calls": rel["failed_calls"],
        "reroutes": rel["reroutes"], "recoveries": rel["recoveries"],
        "terminal_failures": rel["failures"],
        "lost_requests": len(lost),
        "unaccounted_requests": len(unaccounted),
        "accounting_identity_ok": identities_ok,
        "clean_p95_ms": round(clean_p95, 2),
        "chaos_p95_ms": round(p95, 2),
        "p95_inflation": round(p95 / max(clean_p95, 1e-9), 2),
        "clean_wall_s": round(clean_wall, 3),
        "chaos_wall_s": round(wall, 3),
        "breakers": {t: st["tiers"][t]["reliability"].get("breaker")
                     for t in st["tiers"]},
    }
    print(f" chaos: {ist['injected']['error']} errors + "
          f"{ist['injected']['corrupt']} corruptions over {ist['calls']} "
          f"calls ({100 * fault_rate:.1f}%) | retries={rel['retries']} "
          f"reroutes={rel['reroutes']} lost={len(lost)} "
          f"p95 {clean_p95:.1f}ms -> {p95:.1f}ms "
          f"({out['p95_inflation']}x)", flush=True)
    if assert_chaos:
        assert fault_rate >= 0.05, \
            f"injected fault rate {fault_rate:.3f} below the 5% floor"
        assert not lost, f"{len(lost)} admitted requests lost"
        assert not unaccounted, \
            f"{len(unaccounted)} requests finished with no result and no reason"
        assert identities_ok, "faults != retries + failed_calls on some tier"
        assert rel["recoveries"] + rel["recovered_calls"] > 0, \
            "chaos run never exercised a recovery path"
        assert out["p95_inflation"] <= max_p95_inflation, \
            f"p95 inflated {out['p95_inflation']}x under faults " \
            f"(bound {max_p95_inflation}x)"
    return out


def _snapshot_roundtrip(fk, Xte, ytr, fit_s: float,
                        assert_conformant: bool = False) -> dict:
    """Save → load → serve: the loaded engine must answer identically
    without refitting (warm-start in seconds)."""
    import os
    import tempfile
    C = fk.forest.n_classes_
    batch = Xte[:64]
    want = fk.engine.predict(ytr, n_classes=C, X=batch)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "kernel.npz")
        t0 = time.perf_counter()
        fk.save(path)
        save_s = time.perf_counter() - t0
        size = os.path.getsize(path)
        t0 = time.perf_counter()
        fk2 = ForestKernel.load(path)
        load_s = time.perf_counter() - t0
    got = fk2.engine.predict(ytr, n_classes=C, X=batch)
    err = float(np.abs(want - got).max())
    out = {"save_s": round(save_s, 3), "load_s": round(load_s, 3),
           "fit_s": round(fit_s, 3), "bytes": int(size),
           "warmstart_speedup": round(fit_s / max(load_s, 1e-9), 1),
           "predict_max_abs_diff": err}
    print(f" snapshot: save {save_s:.2f}s load {load_s:.2f}s "
          f"({out['warmstart_speedup']}x vs {fit_s:.1f}s fit) "
          f"{size >> 20}MiB  max|Δpredict|={err:.1e}", flush=True)
    if assert_conformant:
        assert err <= 1e-8, f"loaded engine diverges: {err:.2e}"
        assert load_s < max(fit_s, 1.0), \
            "snapshot load slower than refitting"
    return out


def run(n: int = 50_000, d: int = 20, trees: int = 50, backend: str = "auto",
        n_prototypes: int = 20, proto_k: int = 100, n_slots: int = 64,
        n_requests: int = 120, rows_per_request: int = 16,
        sustained: bool = True, slo_ms: float = 500.0,
        escalate_margin: float = 0.2, sustained_rows: int = 8,
        sustained_slots: int = 128, sustained_prefix_depth: int = 6,
        sustained_duration_s: float = 10.0, ratio_target: float = 50.0,
        assert_slo: bool = False, chaos: bool = True,
        chaos_requests: int = 200, chaos_error_rate: float = 0.08,
        assert_chaos: bool = False, snapshot: bool = True,
        obs_overhead: bool = False, obs_overhead_requests: int = 64,
        max_obs_inflation: float = 1.05,
        out_path: str = "BENCH_serving_prox.json") -> dict:
    if backend == "auto":
        backend = "native" if _native.available() else "scipy"
    X, y = gaussian_classes(n + 2000, d=d, n_classes=4, seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, test_frac=2000 / (n + 2000),
                                          seed=0)
    acc_slice = slice(0, min(len(Xte), n_slots))
    report = {"config": {"n": len(Xtr), "d": d, "trees": trees,
                         "backend": backend, "n_prototypes": n_prototypes,
                         "proto_k": proto_k, "n_slots": n_slots,
                         "n_requests": n_requests,
                         "rows_per_request": rows_per_request}}
    t0 = time.perf_counter()
    fk = ForestKernel(kernel_method="gap", n_trees=trees, seed=0,
                      engine_backend=backend).fit(Xtr, ytr)
    report["fit_s"] = round(time.perf_counter() - t0, 1)
    print(f"fitted n={len(Xtr)} trees={trees} backend={backend} "
          f"in {report['fit_s']}s", flush=True)

    t0 = time.perf_counter()
    ce = compress(fk.engine, ytr, n_prototypes=n_prototypes, k=proto_k)
    report["compress_s"] = round(time.perf_counter() - t0, 1)

    reqs = _workload(Xte, n_requests, rows_per_request)
    results = {}
    for name, engine, labels in (("full", fk.engine, ytr),
                                 ("compressed", ce, ce.prototype_labels_)):
        server = ProximityServer(engine, y=labels, n_slots=n_slots)
        res = _drive(server, reqs,
                     yte_for=(Xte[acc_slice], yte[acc_slice]))
        res["memory_bytes"] = int(engine.memory_bytes()["total"])
        res["reference_columns"] = int(engine.W.shape[0])
        results[name] = res
        print(f"{name:>10}: p50 {res['p50_ms']}ms  p95 {res['p95_ms']}ms  "
              f"{res['rows_per_s']} rows/s  mem {res['memory_bytes']>>20}MiB  "
              f"acc {res['oos_accuracy']}", flush=True)

    agree = float((results["full"].pop("oos_labels")
                   == results["compressed"].pop("oos_labels")).mean())
    report.update(results)
    report["compressed_vs_full"] = {
        "predict_agreement": round(agree, 4),
        "p50_speedup": round(results["full"]["p50_ms"]
                             / results["compressed"]["p50_ms"], 2),
        "memory_ratio": round(results["full"]["memory_bytes"]
                              / results["compressed"]["memory_bytes"], 1),
    }
    print("compressed vs full:", json.dumps(report["compressed_vs_full"]),
          flush=True)
    if sustained:
        report["sustained"] = _sustained(
            fk, ce, Xte, ytr, slo_ms=slo_ms, rows=sustained_rows,
            duration_s=sustained_duration_s, ratio_target=ratio_target,
            escalate_margin=escalate_margin, n_slots=sustained_slots,
            prefix_depth=sustained_prefix_depth, assert_slo=assert_slo)
    if chaos:
        report["chaos"] = _chaos(
            fk, ce, Xte, ytr, n_requests=chaos_requests,
            error_rate=chaos_error_rate,
            prefix_depth=sustained_prefix_depth,
            escalate_margin=escalate_margin, assert_chaos=assert_chaos)
    if obs_overhead:
        report["obs_overhead"] = _obs_overhead(
            fk, ce, Xte, ytr, n_requests=obs_overhead_requests,
            max_p95_inflation=max_obs_inflation,
            assert_overhead=assert_slo)
    if snapshot:
        report["snapshot"] = _snapshot_roundtrip(
            fk, Xte, ytr, report["fit_s"], assert_conformant=assert_chaos)
    with open(out_path, "w") as fh:
        json.dump(report, fh, indent=2)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--d", type=int, default=20)
    ap.add_argument("--trees", type=int, default=50)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "scipy", "pallas", "native"])
    ap.add_argument("--prototypes", type=int, default=20)
    ap.add_argument("--proto-k", type=int, default=100)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--requests", type=int, default=120)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--no-sustained", action="store_true",
                    help="skip the Poisson sustained-throughput SLO mode")
    ap.add_argument("--slo-ms", type=float, default=500.0)
    ap.add_argument("--escalate-margin", type=float, default=0.2)
    ap.add_argument("--sustained-rows", type=int, default=8)
    ap.add_argument("--sustained-slots", type=int, default=128)
    ap.add_argument("--sustained-prefix-depth", type=int, default=6)
    ap.add_argument("--duration", type=float, default=10.0,
                    help="sustained-mode offered-load duration (s)")
    ap.add_argument("--ratio-target", type=float, default=50.0,
                    help="offered load as a multiple of the sync baseline")
    ap.add_argument("--assert-slo", action="store_true",
                    help="fail unless p95<=SLO, zero sheds, and >=1 "
                         "escalation agreeing with the full-engine oracle")
    ap.add_argument("--no-chaos", action="store_true",
                    help="skip the fault-injection chaos mode")
    ap.add_argument("--chaos-requests", type=int, default=200)
    ap.add_argument("--chaos-error-rate", type=float, default=0.15)
    ap.add_argument("--assert-chaos", action="store_true",
                    help="fail unless >=5%% of calls fault, zero admitted "
                         "requests are lost, recovery accounting balances, "
                         "p95 inflation is bounded, and the snapshot "
                         "round-trip is conformance-identical")
    ap.add_argument("--no-snapshot", action="store_true",
                    help="skip the snapshot save/load round-trip")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="measure the p95 cost of metrics+tracing vs a "
                         "registry-disabled run (asserted <= the bound "
                         "when combined with --assert-slo)")
    ap.add_argument("--obs-requests", type=int, default=64)
    ap.add_argument("--max-obs-inflation", type=float, default=1.05)
    ap.add_argument("--out", default="BENCH_serving_prox.json")
    args = ap.parse_args()
    configure_compile_cache()
    run(n=args.n, d=args.d, trees=args.trees, backend=args.backend,
        n_prototypes=args.prototypes, proto_k=args.proto_k,
        n_slots=args.slots, n_requests=args.requests,
        rows_per_request=args.rows, sustained=not args.no_sustained,
        slo_ms=args.slo_ms, escalate_margin=args.escalate_margin,
        sustained_rows=args.sustained_rows,
        sustained_slots=args.sustained_slots,
        sustained_prefix_depth=args.sustained_prefix_depth,
        sustained_duration_s=args.duration, ratio_target=args.ratio_target,
        assert_slo=args.assert_slo, chaos=not args.no_chaos,
        chaos_requests=args.chaos_requests,
        chaos_error_rate=args.chaos_error_rate,
        assert_chaos=args.assert_chaos, snapshot=not args.no_snapshot,
        obs_overhead=args.obs_overhead,
        obs_overhead_requests=args.obs_requests,
        max_obs_inflation=args.max_obs_inflation,
        out_path=args.out)


if __name__ == "__main__":
    main()
