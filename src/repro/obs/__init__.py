"""repro.obs — the pool telemetry plane.

Three cooperating pieces, all host-side and jax-free so they can never
perturb a compiled program (the §facade zero-byte invariant):

  * `MetricsRegistry` (obs/metrics.py) — counters / gauges /
    fixed-bucket histograms with online p50/p99.  Every `Pool` owns one;
    the engines, scrubber, straggler policy and recovery paths publish
    into it.
  * `Tracer` (obs/trace.py) — structured JSONL span events whose ids
    tie a fault injection to its recovery solve, re-verify and queued
    follow-ups; `validate_events` checks well-formedness
    (scripts/trace_check.py is the CLI).  Its second sink is the
    profiler: handed an annotation factory (the Pool hands in
    `jax.profiler.TraceAnnotation`), every span is also a `pool.<kind>`
    profiler span, and `Tracer.phase` marks the step path's boundaries
    (`trainer.dispatch`, `pool.commit_dispatch`, `pool.resolve`, ...)
    on the profiler's clock alone, where they line up with the device's
    operations.
  * `HealthReport` (obs/health.py) — green/degraded/critical from the
    window state, straggler drops, scrub findings and syndrome budget;
    `prometheus_text` (obs/export.py) renders the registry for scraping.

Entry points on a live pool: `pool.metrics`, `pool.tracer`,
`pool.stats()`, `pool.health()`; launchers expose --metrics-dir /
--trace-dir.  This module is import-light on purpose (no jax at import
time) — safe to import before XLA flags are set, like repro itself.
"""
from repro.obs.health import CRITICAL, DEGRADED, GREEN, HealthReport
from repro.obs.metrics import (Counter, Gauge, Histogram, LabeledRegistry,
                               MetricsRegistry, default_buckets)
from repro.obs.trace import Tracer, load_jsonl, validate_events
from repro.obs.export import prometheus_text, serve_metrics, write_metrics

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "LabeledRegistry",
    "default_buckets",
    "Tracer", "load_jsonl", "validate_events",
    "HealthReport", "GREEN", "DEGRADED", "CRITICAL",
    "prometheus_text", "serve_metrics", "write_metrics",
]
