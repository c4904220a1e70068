"""Named host spans of the serving engine, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler trace runs, the span lands on the host plane of the same trace
as the device's programs and operations, with ``args`` as its stats;
otherwise entering and leaving it costs about a microsecond.  Pass only
cheap values that are already computed.  ``ServingEngine``'s docstring
lists the spans it records.
"""
from __future__ import annotations

import jax


def span(name, **args):
    """A context manager that records the host span ``name`` with ``args``."""
    return jax.profiler.TraceAnnotation(name, **args)
