"""Named host spans for the profiler trace.

``span(name, **args)`` is a :class:`jax.profiler.TraceAnnotation` named
``repro.<name>``; its arguments (ints or floats) become the event's stats.
A span is recorded only while a profiler session runs
(``jax.profiler.start_trace``/``stop_trace``); otherwise entering one costs
about a microsecond. The spans land in the profiler's host plane, on the
same clock as the device's operations, so each stretch of device idle time
can be put down to the program span open over it. README "Tracing a
running engine" lists the spans and their arguments.
"""
from __future__ import annotations

import jax

__all__ = ["span"]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager recording ``repro.<name>`` with ``args``; more
    arguments can be attached inside it with ``.set_metadata(**args)``."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)
