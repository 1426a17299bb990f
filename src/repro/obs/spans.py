"""Host spans on the profiler's clock.

``span(name)`` is ``jax.profiler.TraceAnnotation``: while a JAX profiler
session runs it records an event on the trace's host plane, on the clock
the device events are aligned to; with no session it records nothing and
costs well under a microsecond.  So the spans are always in place, with no
option to turn them on.  Names are constants in a ``/`` namespace:
``serve/`` for the serving engine, ``mm/`` for the memory manager and
``policy/`` for the policy executor.  A name carries no per-call data, so
spans of one kind sum across a trace.
"""

from __future__ import annotations

import functools

from jax.profiler import TraceAnnotation as span
from jax.profiler import annotate_function


def spanned(name: str):
    """Decorator: every call of the function runs inside ``span(name)``."""
    return functools.partial(annotate_function, name=name)


__all__ = ["span", "spanned"]
