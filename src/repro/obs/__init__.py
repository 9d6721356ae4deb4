"""Unified observability layer: span tracing, executed-vs-scheduled
overlap attribution, and a metrics registry.

Three concerns, one package (DESIGN.md "Observability dataflow"):

  * ``trace``    — ``TraceRecorder``: host-side span enter/exit on a
                   monotonic clock. The engine opens phase spans around
                   its step phases, records request lifecycle spans
                   (submit -> admit -> first token -> finish), and scopes
                   an *active tracer* (contextvar) around its model calls
                   so the DEP executor's task walk emits one span per
                   ATTN/SHARED/GATE/A2E/EXP/E2A/REP task.
  * ``export``   — Chrome-trace/Perfetto JSON: executed spans and the
                   plan's ``ScheduleResult`` intervals as two aligned
                   track groups (predicted-vs-executed Gantt as a
                   loadable artifact), plus the schema validator CI runs.
  * ``overlap``  — reduce executed task spans to per-lane busy/idle and
                   exposed-comm time and diff them against the lowered
                   graph's schedule/``CostBreakdown`` (the "executed
                   overlap == scheduled overlap within eps" metric);
  * ``metrics``  — ``Counter``/``Gauge``/``Histogram`` (fixed log-spaced
                   buckets, p50/p99) behind one ``MetricsRegistry`` that
                   every existing stat surface registers into: one
                   ``snapshot()`` dict, JSONL append export, Prometheus
                   text exposition, and one registry-level ``reset()``
                   that actually clears EWMA residual state everywhere.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               log_buckets, parse_prometheus)
from repro.obs.trace import (Span, TraceRecorder, active_tracer,
                             use_tracer)
from repro.obs.export import (chrome_trace, export_chrome_trace,
                              validate_chrome_trace)
from repro.obs.overlap import (LaneOccupancy, OverlapReport,
                               attribute_overlap, executed_exposed_comm,
                               interval_total, interval_subtract,
                               interval_union, lane_intervals)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "log_buckets",
    "parse_prometheus",
    "Span", "TraceRecorder", "active_tracer", "use_tracer",
    "chrome_trace", "export_chrome_trace", "validate_chrome_trace",
    "LaneOccupancy", "OverlapReport", "attribute_overlap",
    "executed_exposed_comm", "interval_total", "interval_subtract",
    "interval_union", "lane_intervals",
]
