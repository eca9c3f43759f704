"""Unified observability layer: metrics, tracing, profiling hooks.

Three dependency-free pillars shared by serving, the engine, and training:

``obs.metrics``
    Thread-safe :class:`MetricsRegistry` of Counter / Gauge / Histogram
    families (labeled children, log-spaced latency buckets, exact
    percentiles from a bounded sample reservoir), JSON snapshots and
    Prometheus text exposition, plus the shared :class:`EWMA` primitive.

``obs.trace``
    ``span(name, **stats)`` — in-process phases of one thread as
    ``repro:<name>`` spans on the JAX profiler's clock (the engine's
    routing, upload, dispatch and fetch); and per-request span trees on an
    injectable clock, sampled into a bounded ring buffer, exportable as
    Chrome ``chrome://tracing`` JSON.

``obs.profile``
    ``instrument(engine)`` — a transparent proxy timing every
    ``ProximityEngine`` op into ``engine_op_seconds{op,backend,tier}``
    and mirroring qs-cache hit/miss gauges.

A process-wide default registry (``metrics.global_registry()``) collects
the training / snapshot profiling hooks; the serving stack owns explicit
registries (one per server ladder) so benchmarks can run an identical
workload with observability on and off.
"""
from .http import EXPOSITION_CONTENT_TYPE, MetricsHTTPServer
from .metrics import (EWMA, Counter, Gauge, Histogram, MetricsRegistry,
                      global_registry, parse_exposition)
from .profile import InstrumentedEngine, instrument
from .trace import NULL_SPAN, Span, Tracer, span

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram", "EWMA",
           "global_registry", "parse_exposition", "Tracer", "Span",
           "NULL_SPAN", "span", "instrument", "InstrumentedEngine",
           "MetricsHTTPServer", "EXPOSITION_CONTENT_TYPE"]
