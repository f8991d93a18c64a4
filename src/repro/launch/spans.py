"""Host spans of the serving loop, on the profiler's clock.

``span(name, counters, **ids)`` marks one phase of host work. Under a
running ``jax.profiler`` trace it is a ``TraceAnnotation`` named ``name``
with ``ids`` attached, on the same clock as the device's op events; with
no profiler running the annotation is inert. Either way the phase's
``time.perf_counter()`` seconds and one count are added to
``counters[name]`` as a ``(seconds, count)`` pair, which the owner of
``counters`` reports without a profiler (``GPFieldServer.metrics()``'s
``phase_s`` and ``phase_n``).

This is the program's only tracing helper; device-side phases are named
with ``jax.named_scope`` where the ops are built.
"""
from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def span(name: str, counters: dict, **ids):
    """Time the body as phase ``name``; ``ids`` (numbers or short strings
    without ``,``, ``=`` or ``#``) label the profiler's event, and the
    annotation it yields takes more through ``set_metadata(**ids)``. The
    body's seconds count whether it returns or raises."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **ids) as ann:
            yield ann
    finally:
        s, n = counters.get(name, (0.0, 0))
        counters[name] = (s + time.perf_counter() - t0, n + 1)
