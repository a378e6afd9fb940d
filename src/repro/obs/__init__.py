"""Observability layer (DESIGN.md §12).

Two clocks, kept apart:

* simulated seconds — ``Tracer`` records the event runtime's round
  lifecycle (a result of the simulation), the metric registry backs
  ``runtime.stats``, and ``export`` writes Perfetto/JSONL timelines;
* wall seconds — ``span`` (``obs/span.py``) puts the simulator's own host
  work into a ``jax.profiler`` trace as ``asyncfleo.*`` spans, beside the
  device's operations and the fused program's named scopes.

Everything here is read-only with respect to simulation state:
``tracer=None`` runs, and runs with no profiler trace, are bit-identical
and pay nothing measurable."""
from repro.obs.export import (add_runtime_tracks, export_chrome,
                              export_jsonl, validate_chrome_trace)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricRegistry,
                               StatsView)
from repro.obs.span import SPAN_PREFIX, span, tracing
from repro.obs.trace import NULL_TRACER, Instant, NullTracer, Span, Tracer

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span", "Instant",
    "Counter", "Gauge", "Histogram", "MetricRegistry", "StatsView",
    "SPAN_PREFIX", "span", "tracing",
    "export_chrome", "export_jsonl", "validate_chrome_trace",
    "add_runtime_tracks",
]
