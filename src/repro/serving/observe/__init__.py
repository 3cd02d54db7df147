"""Serving observability: span tracing and typed metrics.

Two host-side instruments threaded through the serving stack (none may
introduce recompiles — the traced CI smoke asserts zero):

* ``Tracer`` / ``NullTracer`` — nested ``serve.*`` scopes and per-request
  spans on the scheduler's injectable clock, mirrored into the JAX
  profiler's trace while enabled, exportable as Chrome trace-event JSON
  (``chrome://tracing`` / Perfetto) or JSONL.
* ``MetricsRegistry`` with ``Counter`` / ``Gauge`` / ``Histogram`` —
  Prometheus-style text exposition + JSON snapshot; ``ServerStats``
  mirrors its funnel/pool/spec/resilience counters into one.
"""
from repro.serving.observe.metrics import (Counter, Gauge, Histogram,
                                           MetricsRegistry)
from repro.serving.observe.trace import (NULL_TRACER, SCHED_TID, NullTracer,
                                         Tracer)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "SCHED_TID",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
]
