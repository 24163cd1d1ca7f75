"""Host spans on the profiler's clock.

``span(name, **counts)`` marks a stretch of host work as a
``jax.profiler.TraceAnnotation``: it lands in the same ``.xplane.pb`` as
the device's operations, with ``counts`` as stats on the span (and any set
later through ``set_metadata`` before it closes). It records only while a
profiler session runs (``jax.profiler.start_trace``); otherwise entering
and leaving it is a check of one flag. The names the serving path emits,
and who reads each, are listed in docs/serving.md ("Tracing a live
server").
"""
from __future__ import annotations

import jax


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with integer ``counts`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **counts)
