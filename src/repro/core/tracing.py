"""Spans of the served path, written into the JAX profiler's own trace.

``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: while a
profiler session is active, the span lands in the trace beside the
device's operations and on the same clock, with ``args`` (and whatever
``set_metadata`` adds before it closes) as the event's stats; with no
session active it is a no-op TraceMe of about a microsecond.  There is
no switch: the spans are always there, and a profiler session records
them.

The names are the trace's interface.  Readers of the device trace
(``benchmarks/chip/metrics``) match on them, so a span keeps its name
when the code around it moves:

- ``middleware.dispatch``: ``Rhapsody`` admits, routes and enqueues one
  INFERENCE task;
- ``replica_set.route``: the replica set picks an endpoint;
- ``service.admit``: a replica's loop moves requests from its endpoint
  queue into the servicer;
- ``service.resolve``: a replica's loop resolves the futures of a step's
  results;
- ``servicer.step``: one ``LLMServicer.step`` that has work;
- ``engine.admit``, ``engine.prefill``, ``engine.decode``,
  ``engine.collect``: the paged engine's phases of one step;
- ``engine.prepare``: block tables and the host arrays of one call;
- ``engine.dispatch``: the uploads and the jitted call;
- ``engine.sample``: token choice and the blocking readback;
- ``python.gc``: one garbage collection (``trace_gc``).
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation as span

__all__ = ["span", "trace_gc"]

# the open ``python.gc`` span: the collector is process-wide and its
# collections never overlap, so one slot serves every thread
_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if span.is_enabled():
            s = span("python.gc", generation=info["generation"])
            s.__enter__()
            _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def trace_gc() -> None:
    """Span every garbage collection of this process as ``python.gc``.
    Installing it again does nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
