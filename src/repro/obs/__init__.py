"""Telemetry subsystem (DESIGN.md sec. 13): three layers.

1. In-program per-level traces: `BFSConfig(telemetry=True)` threads a
   per-level carry through the engine's `lax.while_loop`; every search
   returns a `LevelTrace` (frontier counts, direction, fold wire bytes,
   expand/fold/exchange work stamps), also readable as
   `GraphSession.last_trace()`.  Off by default; the flag keys every
   engine/AOT cache, so the off path compiles to exactly the untraced
   program and outputs are bit-identical either way.

2. The metrics registry: thread-safe labeled counters / gauges /
   histograms (`MetricsRegistry`), JSON + Prometheus-text exposition
   (`MetricsRegistry.snapshot`, `to_prometheus`) and the JSONL
   `EventLog`.  Every `GraphServer` owns one registry, so counters reset
   with the server.

3. Request tracing in `repro.serve`: span-per-request lifecycle
   (admit -> queue -> coalesce -> execute -> demux) on each
   `QueryResult.trace`, feeding the registry's latency histograms.
"""
from repro.obs.export import EventLog, to_prometheus
from repro.obs.metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.spans import PHASES, RequestTrace, Span, request_trace
from repro.obs.trace import (N_TRACE_OUTS, RECORDED_LEVELS, TRACE_CHANNELS,
                             LevelTrace, assemble_traces, init_trace,
                             normalize_aux, record_level, trace_outputs)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "DEFAULT_BUCKETS",
    "EventLog", "to_prometheus",
    "LevelTrace", "assemble_traces", "init_trace", "normalize_aux",
    "record_level", "trace_outputs", "TRACE_CHANNELS", "N_TRACE_OUTS",
    "RECORDED_LEVELS",
    "RequestTrace", "Span", "PHASES", "request_trace",
]
