"""Named spans on the profiler's clock.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: under
``jax.profiler.trace`` it lands in the same ``.xplane.pb`` as the device
planes, so a span and the programs it launched share one clock, and with
the profiler off it costs about a microsecond.  When JAX has not been
imported, ``span`` is a null context and imports nothing: the sim and host
discovery paths run without JAX.

Span names start with ``mt4g.``; one span stands for one event.
"""
from __future__ import annotations

import contextlib
import sys

__all__ = ["span"]

_NULL = contextlib.nullcontext()


def span(name: str):
    """A ``TraceAnnotation`` named ``name`` if JAX is loaded, else a null
    context."""
    jax = sys.modules.get("jax")
    return _NULL if jax is None else jax.profiler.TraceAnnotation(name)
