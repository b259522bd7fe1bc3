"""End-to-end observability for the serving stack: span tracing +
unified metrics (see docs/observability.md).

  * ``Tracer`` / ``Span`` — bounded-ring span tracer with Chrome
    trace-event / Perfetto export; sessions enable it with
    ``SessionConfig(trace=True)`` and read it via
    ``MonitorSession.tracer`` / ``export_trace``; ``span(tracer, ...)``
    opens a live span (ring entry + profiler annotation) or, with no
    tracer, the no-op one.
  * ``MetricsRegistry`` / ``Counter`` / ``Gauge`` — the counter / gauge
    / histogram registry behind ``MonitorSession.metrics()`` and the
    correction server's heartbeat snapshot.
  * ``validate_chrome_trace`` / ``load_trace`` — the trace-event schema
    gate (CI trace-smoke, ``tools/trace_report.py``).
"""
from repro.observability.metrics import (Counter, Gauge, MetricsRegistry,
                                         flatten)
from repro.observability.report import breakdown, breakdown_table
from repro.observability.trace import (Span, Tracer, load_trace, span,
                                       validate_chrome_trace)

__all__ = ["Counter", "Gauge", "MetricsRegistry", "flatten",
           "Span", "Tracer", "breakdown", "breakdown_table",
           "load_trace", "span", "validate_chrome_trace"]
