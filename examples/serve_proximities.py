"""Out-of-sample proximity serving end-to-end: fit a forest kernel, warm the
application states, prototype-compress it, then serve a mixed request stream
(predict / topk / outlier / propagate / embed) through the continuous-batching
``ProximityServer`` and compare the full and compressed models.  Ends with
the observability layer: a per-tier latency table read from the shared
metrics registry, a Prometheus exposition dump, and a Chrome-trace JSON
(open ``chrome://tracing`` or https://ui.perfetto.dev and load it) showing
each request's causal path through the tier ladder.

  PYTHONPATH=src python examples/serve_proximities.py [--n 4000]
      [--trees 30] [--backend auto] [--slots 32] [--trace-out trace.json]
"""
import argparse
import json

import numpy as np

from repro.applications.embed import ProximityEmbedding
from repro.applications.prototypes import compress
from repro.core.api import ForestKernel
from repro.data.synthetic import gaussian_classes, train_test_split
from repro.forest import _native


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--d", type=int, default=12)
    ap.add_argument("--trees", type=int, default=30)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "scipy", "pallas", "native"])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--trace-out", default="trace.json",
                    help="Chrome-trace JSON output path ('' to skip)")
    ap.add_argument("--metrics-out", default="",
                    help="optional path for the Prometheus exposition dump")
    args = ap.parse_args()
    backend = args.backend
    if backend == "auto":
        backend = "native" if _native.available() else "scipy"

    X, y = gaussian_classes(args.n, d=args.d, n_classes=4, sep=3.0, seed=0)
    Xtr, ytr, Xte, yte = train_test_split(X, y, test_frac=0.2, seed=0)
    fk = ForestKernel(kernel_method="gap", n_trees=args.trees, seed=0,
                      engine_backend=backend).fit(Xtr, ytr)
    print(f"fitted: {len(Xtr)} samples, {args.trees} trees, "
          f"engine backend={backend}")

    # serving-side application states: warm-started propagation + embedding
    rng = np.random.default_rng(0)
    labeled = rng.random(len(ytr)) < 0.1
    propagator = fk.propagate_labels(labeled, online=True)
    embedding = ProximityEmbedding(n_components=2).fit(fk.engine)

    # 1. full-engine server: mixed request stream
    srv = fk.serve(n_slots=args.slots, propagator=propagator,
                   embedding=embedding)
    reqs = [("predict", Xte[:16]), ("topk", Xte[16:24], 5),
            ("outlier", Xte[24:40]), ("propagate", Xte[40:56]),
            ("embed", Xte[56:72]), ("predict", Xte[72:88])]
    res = srv.serve(reqs)
    acc = np.mean(np.concatenate([res[0]["labels"], res[5]["labels"]])
                  == np.concatenate([yte[:16], yte[72:88]]))
    st = srv.stats()
    print(f"full engine: {st['requests']} requests / {st['rows']} rows in "
          f"{st['ticks']} ticks, predict acc {acc:.3f}")
    for kind, ks in sorted(st["kinds"].items()):
        print(f"  {kind:>9}: n={ks['requests']}  p50 {ks['p50_ms']:.2f}ms  "
              f"p95 {ks['p95_ms']:.2f}ms")
    assert acc > 0.9, "full-engine serving must predict accurately"

    # 2. prototype compression: low-memory serving model
    ce = compress(fk.engine, ytr, n_prototypes=10, k=60)
    ratio = fk.engine.memory_bytes()["total"] / ce.memory_bytes()["total"]
    print(f"compressed: {ce.W.shape[0]} prototype columns vs "
          f"{fk.engine.W.shape[0]} training columns "
          f"({ratio:.1f}x smaller factors, per-class coverage "
          f"{ {c: round(v, 2) for c, v in ce.coverage_.items()} })")

    # 3. compressed server agrees with the full model on what it serves
    srv_c = fk.serve(n_slots=args.slots, engine=ce)
    got = srv_c.serve([("predict", Xte[:32]), ("topk", Xte[:8], 3)])
    full_labels = srv.serve([("predict", Xte[:32])])[0]["labels"]
    agree = (got[0]["labels"] == full_labels).mean()
    acc_c = (got[0]["labels"] == yte[:32]).mean()
    print(f"compressed serving: predict agreement {agree:.3f} vs full, "
          f"accuracy {acc_c:.3f}; topk serves training-row ids "
          f"{got[1]['indices'][0]}")
    assert agree >= 0.85, "compression must roughly preserve predictions"

    # 4. tiered serving: shallow -> compressed -> full ladder with
    #    confidence escalation, deadlines, and observability counters
    tsrv = fk.serve_tiered(prefix_depth=4, compressed_engine=ce,
                           n_slots=args.slots, escalate_margin=0.3,
                           propagator=propagator, embedding=embedding)
    tres = tsrv.serve([("predict", Xte[:32]), ("topk", Xte[:8], 5),
                       ("predict", Xte[32:64]), ("embed", Xte[64:80]),
                       ("outlier", Xte[80:96])])
    tacc = np.mean(np.concatenate([tres[0]["labels"], tres[2]["labels"]])
                   == np.concatenate([yte[:32], yte[32:64]]))
    ts = tsrv.stats()
    print(f"tiered serving: {ts['requests']} requests, predict acc "
          f"{tacc:.3f}, escalations {ts['escalations']} "
          f"(rate {ts['escalation_rate']:.2f}), shed {ts['shed']}, "
          f"timeouts {ts['timeouts']}")
    for name, tstat in ts["tiers"].items():
        qc = tstat["qs_cache"]
        print(f"  tier {name:>10}: routed={tstat['routed_requests']}  "
              f"shed={tstat['shed']}  qs-cache "
              f"{qc['hits']}/{qc['hits'] + qc['misses']} hits "
              f"(rate {qc['hit_rate']:.2f})")
    assert tacc > 0.9, "tiered serving must predict accurately"

    # 5. observability: per-tier latency table from the shared registry,
    #    Prometheus exposition, and a Chrome-trace of the request spans
    from repro.obs.metrics import parse_exposition
    print("per-tier latency (registry histograms):")
    print(f"  {'tier':>10} {'kind':>9} {'n':>5} {'p50 ms':>8} "
          f"{'p95 ms':>8} {'p99 ms':>8}")
    for name, tstat in ts["tiers"].items():
        for kind, ks in sorted(tstat["kinds"].items()):
            h = tsrv.registry.histogram(
                "serve_request_seconds",
                labels=("tier", "kind")).labels(tier=name, kind=kind)
            print(f"  {name:>10} {kind:>9} {ks['requests']:>5} "
                  f"{ks['p50_ms']:>8.2f} {ks['p95_ms']:>8.2f} "
                  f"{h.percentile(99) * 1e3:>8.2f}")
    text = tsrv.registry.exposition()
    series = parse_exposition(text)
    print(f"prometheus exposition: {len(text.splitlines())} lines, "
          f"{len(series)} series (round-trip parsed)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            fh.write(text)
        print(f"  wrote {args.metrics_out}")
    if args.trace_out:
        obj = tsrv.tracer.export(args.trace_out)
        n_spans = sum(1 for e in obj["traceEvents"] if e["ph"] == "X")
        print(f"chrome trace: {len(tsrv.tracer.spans())} requests, "
              f"{n_spans} spans, {len(obj['traceEvents'])} events "
              f"-> {args.trace_out}")
        with open(args.trace_out) as fh:     # well-formed JSON on disk
            json.load(fh)
    print("OK")


if __name__ == "__main__":
    main()
