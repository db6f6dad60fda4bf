"""repro.obs — end-to-end search/serve/train observability (ISSUE 6 + 7).

Offline half (ISSUE 6):
  registry   — counters / gauges / fixed-bucket histograms; JSON +
               Prometheus-text export (``get_registry()``)
  trace      — host-side ``span()`` → chrome://tracing JSONL and, while
               ``jax.profiler`` traces, the profiler's host plane
               (``get_tracer()``)
  telemetry  — ``SearchTelemetry`` pytree accumulated inside the jitted
               search loops + host-side recording/warnings

Online half (ISSUE 7):
  exporter   — ``MetricsExporter``: /metrics (Prometheus), /metrics.json,
               /healthz, /debug/telemetry over stdlib http.server
  window     — ``RollingWindow``: last-N-batches SLO aggregates
               (latency p50/p95/p99, entry-quality quantiles, eviction rates)
  adaptive   — ``AdaptiveController``: telemetry-driven beam/max_hops ladder
               stepping over precompiled static configs

Per-query half (ISSUE 8):
  router     — ``HardnessRouter``: splits each batch by predicted hardness
               and runs each side at a different precompiled ladder rung
               (``GateIndex.search_routed``); ``registry_sink`` is the
               default ``telemetry_sink`` of the SearchParams API

See docs/observability.md.
"""
from repro.obs.adaptive import (
    AdaptiveController,
    DEFAULT_LADDER,
    LadderRung,
    VotePolicy,
)
from repro.obs.exporter import MetricsExporter
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS,
    MetricsRegistry,
    POW2_BUCKETS,
    get_registry,
)
from repro.obs.router import HardnessRouter, RouteReport, route_buckets
from repro.obs.telemetry import (
    RATIO_BUCKETS,
    SearchTelemetry,
    call_telemetry_sink,
    chain_sinks,
    record_search_telemetry,
    registry_sink,
    summarize,
    warn_on_ring_overflow,
)
from repro.obs.trace import Tracer, get_tracer, read_trace, span
from repro.obs.window import RollingWindow

__all__ = [
    "AdaptiveController",
    "Counter",
    "DEFAULT_LADDER",
    "Gauge",
    "HardnessRouter",
    "Histogram",
    "LATENCY_BUCKETS",
    "LadderRung",
    "MetricsExporter",
    "MetricsRegistry",
    "POW2_BUCKETS",
    "RATIO_BUCKETS",
    "RollingWindow",
    "RouteReport",
    "SearchTelemetry",
    "Tracer",
    "VotePolicy",
    "call_telemetry_sink",
    "chain_sinks",
    "get_registry",
    "get_tracer",
    "read_trace",
    "record_search_telemetry",
    "registry_sink",
    "route_buckets",
    "span",
    "summarize",
    "warn_on_ring_overflow",
]
