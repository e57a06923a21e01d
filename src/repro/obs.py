"""The program's own observability: named spans and plain counters.

``span(name, **fields)`` is a ``jax.profiler.TraceAnnotation``.  While a
profiler trace is being captured (``jax.profiler.trace``) it lands in the
trace's host plane, on the clock the device's ops use, with ``fields`` as
the event's stats; otherwise it costs a check.  There is no exporter, no
second clock and no buffer: the profiler is the store.

Counters are plain ``collections.Counter`` objects, owned by what they
count (``ContinuousBatchingEngine.counters``): a name reads 0 until
something adds to it, and ``after - before`` is what was counted in
between.

``routes`` counts, at trace time, each choice a dispatch guard made and
why (``gemm.tile.gemm2d.64x512``, ``lut_brick.factored``,
``moe.grouped.taken``, ...): one count per trace of the function that asked, not per call.  It is the
process's, because a guard is a pure function of shapes and policy with
no owner to hold it.
"""
from __future__ import annotations

import collections

import jax

routes = collections.Counter()


def span(name: str, **fields):
    """A named span on the profiler's timeline (docs/serving.md,
    "Observing the engine")."""
    return jax.profiler.TraceAnnotation(name, **fields)


def route(guard: str, choice: str) -> bool:
    """Record one dispatch decision of ``guard``: ``choice`` is "taken"
    or "refused.<reason>".  Returns whether the route was taken, so a
    guard can end with ``return route(...)``."""
    routes[f"{guard}.{choice}"] += 1
    return choice == "taken"
