"""Named host spans of the serving engines, in the profiler's own trace.

``span(name, **counters)`` is ``jax.profiler.TraceAnnotation``: with a
profiler trace running, the span lands on the host line of the thread that
opened it, in the same ``.xplane.pb`` and on the same clock as the device
ops, and each keyword becomes an event stat of the span.  With no trace
running it costs under a microsecond.

Counter values are ints the caller already holds: the keywords are built
with no trace running too, so a counter that needs work of its own to
compute costs that work on every call.  The names the engines use (``serve.*``) are a
contract with whatever reads the trace; a wait on the device gets a name
ending in ``_sync`` and nothing else does.
"""
from __future__ import annotations

import jax


def span(name: str, **counters) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` with ``counters`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **counters)
