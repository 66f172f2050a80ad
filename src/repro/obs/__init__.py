"""repro.obs -- the observability plane (tracing + live metrics).

Two halves:

  * **Tracing** (off by default; ``TRIDENT_TRACE=1`` /
    ``PartyCluster(trace=True)`` / ``netbench --trace``): every
    instrumented seam records span/instant events that merge into one
    Perfetto-viewable cluster timeline; ``Tracer(sink="profiler")``
    puts the spans into the JAX profiler's trace instead.
  * **Live metrics** (always on): the same seams update a process-local
    ``MetricsRegistry`` -- counters, gauges, fixed-edge histograms --
    unconditionally; ``TRIDENT_METRICS=1`` / ``PartyCluster(metrics=True)``
    additionally serves each daemon's registry over a tiny HTTP exporter,
    and ``health.cluster_health`` scrapes all five into one health doc.

See docs/OBSERVABILITY.md for the span/metric taxonomy and workflows.
"""
from repro.obs.merge import merge_chunks, write_chrome_trace
from repro.obs.metrics import metrics_snapshot, round_wall_ms
from repro.obs.registry import (
    METRICS_ENV,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    install_registry,
    metrics_enabled,
    snapshot_link_bits,
    snapshot_total,
    snapshot_updated,
    snapshot_value,
)
from repro.obs.tracer import (
    NULL_SPAN,
    NULL_TRACER,
    PROFILER_PREFIX,
    RECV_SPAN_MIN_S,
    NullTracer,
    Stopwatch,
    TRACE_ENV,
    Tracer,
    ensure_tracer,
    get_tracer,
    install_tracer,
    stopwatch,
    timed,
    traced_protocol,
    tracing_enabled,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "METRICS_ENV",
    "MetricsRegistry",
    "NULL_SPAN",
    "NULL_TRACER",
    "NullTracer",
    "PROFILER_PREFIX",
    "RECV_SPAN_MIN_S",
    "Stopwatch",
    "TRACE_ENV",
    "Tracer",
    "ensure_tracer",
    "get_registry",
    "get_tracer",
    "install_registry",
    "install_tracer",
    "merge_chunks",
    "metrics_enabled",
    "metrics_snapshot",
    "round_wall_ms",
    "snapshot_link_bits",
    "snapshot_total",
    "snapshot_updated",
    "snapshot_value",
    "stopwatch",
    "timed",
    "traced_protocol",
    "tracing_enabled",
    "write_chrome_trace",
]
