"""Spans on the profiler's clock.

:func:`span` opens a ``jax.profiler.TraceAnnotation``: when a profiler
session is running (``jax.profiler.trace`` / ``start_trace``), the span
lands in the same trace as the device's op and program lines, on the
same clock; when none is running it costs one TraceMe check.  Span names
are fixed strings (the serving loop's are listed in this package's
README); per-request ids ride as keyword arguments (``uid=``), which the
trace records as the span's stats, never in the name.

JAX is imported when a span is opened, not when this module is, so
``import repro.obs`` stays free of JAX.
"""
from __future__ import annotations


def span(name: str, **ids):
    """A context manager that records ``name`` as a host span in an
    active profiler trace, with ``ids`` as its stats."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **ids)
