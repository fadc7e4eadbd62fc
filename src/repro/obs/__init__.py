"""Unified telemetry: metrics registry, tracer, training telemetry.

Observability for the serving + training stack:

* ``obs.metrics`` — live counters/gauges/histograms with labeled series,
  JSON snapshot + Prometheus text exposition, and the shared
  percentile/SLO helpers the stats dicts build on.
* ``obs.trace`` — the engine's tick-phase spans, always written into a
  ``jax.profiler`` trace (``TraceAnnotation``) and, with a ``Tracer``,
  exported with the per-request lifecycle as Chrome trace-event /
  Perfetto JSON.
* ``obs.profile`` — the training telemetry JSONL stream and the
  block-sparsity measurements it reports.

The registry is live by default (``NULL_REGISTRY`` strips it); the
Chrome tracer is off by default (``NULL_TRACER``), which leaves each
engine span a profiler annotation only.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_REGISTRY, NullRegistry, parse_prometheus,
                               pct, prom_value, slo_summary)
from repro.obs.profile import (TrainTelemetry, group_l1_penalty,
                               layer_block_sparsity, sparsity_telemetry_fn,
                               total_block_sparsity)
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer, \
    validate_chrome_trace

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullRegistry",
    "NULL_REGISTRY", "parse_prometheus", "pct", "prom_value", "slo_summary",
    "TrainTelemetry", "group_l1_penalty",
    "layer_block_sparsity", "sparsity_telemetry_fn", "total_block_sparsity",
    "Tracer", "NullTracer", "NULL_TRACER", "validate_chrome_trace",
]
