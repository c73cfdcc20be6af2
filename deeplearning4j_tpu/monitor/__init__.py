"""Unified observability: pull metrics + span tracing, zero external deps.

Two stores, one subsystem:

- ``metrics`` — a process-wide ``MetricsRegistry`` of Counter / Gauge /
  fixed-bucket Histogram families (labels supported) rendered in the
  Prometheus text exposition format. Scraped at ``GET /metrics`` on the
  serving server; read in-process by ``/stats``, the UI StatsListener and
  bench row snapshots — all the same numbers, so surfaces cannot drift.
- ``tracing`` — a ring-buffered span tracer (``with trace.span("dispatch")``)
  exporting Chrome trace-event JSON for Perfetto; spans cover the train
  loop (wait/fetch/h2d/dispatch/callback) and the serving path
  (enqueue/bucket/pad/device/readback).

Fleet additions (docs/OBSERVABILITY.md):

- ``tracing.TraceContext`` — Dapper-style trace identity minted at the
  router, propagated via ``x-trace-context``; tracer timestamps share
  the wall-clock epoch so ``collect.collect_fleet_trace`` can merge
  every process's ring buffer into ONE Perfetto document.
- ``slo.BurnRateSLO`` — multi-window (5 m / 1 h) error-budget burn-rate
  health, wired into router and replica ``/healthz``.
- ``profiling`` — ``POST /admin/profile`` around live traffic and
  ``DL4JTPU_PROFILE=dir`` around ``fit()``.
- ``reqlog`` — the wide-event request journal: one terminal record per
  request (phases, outcome, spec/KV accounting), served at
  ``GET /requests`` and merged fleet-wide by ``collect.collect_requests``
  (docs/OBSERVABILITY.md "Request lifecycle").
- ``flight`` — the training flight recorder: per-layer telemetry
  computed inside the jitted train step, a crash-safe ring of recent
  records (``GET /train/diagnostics``), anomaly detection, Perfetto
  counter tracks (``collect.flight_counter_events``).

Both stores are cheap enough to leave on (see the bench's
``observability`` row); tracing is opt-in via ``trace.enable()`` /
``DL4JTPU_TRACE``. Metric name catalog and usage in
docs/OBSERVABILITY.md (linted by tools/lint_metrics.py).
"""

from deeplearning4j_tpu.monitor.metrics import (
    Counter, Gauge, Histogram, MetricsRegistry, get_registry,
    set_metrics_enabled, DEFAULT_LATENCY_BUCKETS, DEFAULT_STEP_BUCKETS)
from deeplearning4j_tpu.monitor.tracing import (
    Tracer, trace, get_tracer,
    TraceContext, set_context, get_context, trace_context)
from deeplearning4j_tpu.monitor.slo import BurnRateSLO, SLOState
from deeplearning4j_tpu.monitor.collect import (
    collect_fleet_trace, collect_requests, merge_docs,
    flight_counter_events)
from deeplearning4j_tpu.monitor.reqlog import RequestLog, new_record
from deeplearning4j_tpu.monitor.flight import (
    FlightRecorder, AnomalyDetector, STAT_COLS)
from deeplearning4j_tpu.monitor.profiling import (
    start_profile, profile_status, profile_scope)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "set_metrics_enabled",
    "DEFAULT_LATENCY_BUCKETS", "DEFAULT_STEP_BUCKETS",
    "Tracer", "trace", "get_tracer",
    "TraceContext", "set_context", "get_context", "trace_context",
    "BurnRateSLO", "SLOState",
    "collect_fleet_trace", "collect_requests", "merge_docs",
    "flight_counter_events", "RequestLog", "new_record",
    "FlightRecorder", "AnomalyDetector", "STAT_COLS",
    "start_profile", "profile_status", "profile_scope",
]
