"""End-to-end observability: metrics registry, span tracing, exposition.

  metrics.py -- counters / gauges / mergeable log-bucketed histograms
                (O(1) memory, exact quantile bounds) with label support;
                Prometheus text + JSON snapshot exposition
  trace.py   -- per-micro-batch span trees over the query cascade
                (plan > schedule > dispatch > scan/prune > delta > rerank
                > collect/merge > compaction), bounded ring buffer,
                deterministic sampling, Chrome trace-event export
  http.py    -- stdlib HTTP server exposing /metrics, /metrics.json,
                /traces, /healthz (launch/serve.py --metrics-port)

The metric catalog lives in docs/OBSERVABILITY.md and is kept in exact
sync with the runtime registrations by tools/check_metrics.py (CI).
"""

from repro.obs.metrics import (
    GROWTH,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_REGISTRY,
    PROCESS_REGISTRY,
    NullRegistry,
)
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Span, Tracer

__all__ = [
    "GROWTH",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "NULL_REGISTRY",
    "PROCESS_REGISTRY",
    "Span",
    "Tracer",
    "NULL_SPAN",
    "NULL_TRACER",
]
