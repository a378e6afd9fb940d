"""Wall-clock spans of the simulator, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``asyncfleo.<name>`` carrying ``args`` as its metadata, so a profiler
trace (``jax.profiler.trace``) shows the program's host work on the same
clock as the device's operations.  With no trace running it records
nothing and returns a shared no-op context: the guard costs a call, and
the annotation's arguments are never encoded.  A caller whose arguments
cost more than the span to compute checks :func:`tracing` first.

The simulated-time lifecycle (``obs/trace.Tracer``) is a result of the
simulation; these spans measure the simulator itself (DESIGN.md §12).
"""
from __future__ import annotations

import contextlib

import jax

SPAN_PREFIX = "asyncfleo."

_OFF = contextlib.nullcontext()
tracing = jax.profiler.TraceAnnotation.is_enabled


def span(name: str, **args):
    """A host span ``asyncfleo.<name>`` while a profiler trace runs."""
    if not tracing():
        return _OFF
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
