"""repro.obs — tracing, metrics export and termination explainability.

The observability layer of the serving stack (docs/observability.md):

  * ``obs.trace``   — per-query lifecycle spans + the device-side
                      predicted-recall trajectory ring the serve chunk
                      jits carry (zero extra syncs, no retraces), and
                      the serve loop's profiler spans (``span``,
                      ``SERVE_SPANS``) and device scope names
                      (``DEVICE_SCOPES``) on the profiler's clock;
  * ``obs.metrics`` — counters / gauges / fixed-bucket histograms with
                      Prometheus text exposition and a JSONL event log;
  * ``obs.explain`` — reconstruct any query's story from a trace
                      (``python -m repro.obs.explain``);
  * ``obs.stats``   — the one shared p50/p99 percentile helper
                      (conservative tails, NaN on empty).
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               serve_metrics)
from repro.obs.stats import p01, p50, p99, percentile, summarize
from repro.obs.trace import (DEVICE_SCOPES, NO_PREDICTION, SERVE_SPANS,
                             TERMINATION_REASONS, Span, Tracer, load_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "serve_metrics",
    "p01", "p50", "p99", "percentile", "summarize",
    "NO_PREDICTION", "TERMINATION_REASONS", "Span", "Tracer", "load_trace",
    "SERVE_SPANS", "DEVICE_SCOPES",
]
