"""Named host spans on the profiler's own clock.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation`` named
``memo.<name>``. Under ``jax.profiler.trace`` (or ``start_trace``) the
profiler keeps each span in memory and writes it out with the device's
operations, so a span and the device work it issued share one clock.
With no profiler session running a span costs well under a microsecond.

Spans mark the serving path's phases (``MemoServer`` and the engine
step) and the maintenance worker's payloads. ``args`` are ints the
caller already holds; a span never syncs with the device.
"""
from __future__ import annotations

import jax

PREFIX = "memo."


def span(name: str, **args: int):
    """The span ``memo.<name>``, as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
