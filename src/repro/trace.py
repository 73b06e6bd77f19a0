"""Host spans at the store's layer boundaries, on the profiler's clock.

``span(name)`` marks one call at a layer boundary: a retrieval, a block
fetch, a decode, a host fold, a device dispatch.  Off (the default) it
returns one shared no-op context, so a call site costs a global check
and nothing else, and this module imports no JAX.  On, it returns a
``jax.profiler.TraceAnnotation``: while a ``jax.profiler`` trace runs,
each span lands in the profiler's host plane, on the thread that opened
it and on the same clock as the device's operations, and the profiler
writes it out at ``stop_trace``.  A span's parent is the span that
encloses it on the same thread.

Span names are ``<module>.<what>`` (``tgi.fetch_delta``,
``serialize.decode``); docs/api.md lists them.

    from repro import trace

    trace.enable()
    jax.profiler.start_trace(log_dir)
    ...                       # queries whose spans the trace should hold
    jax.profiler.stop_trace()
    trace.enable(False)
"""
from __future__ import annotations

import contextlib

_ON = False
_OFF = contextlib.nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, bound by enable()


def span(name: str):
    """A context manager that marks one call as the span ``name``."""
    if not _ON:
        return _OFF
    return _annotation(name)


def enable(on: bool = True) -> bool:
    """Turn spans on (or off) for the whole process; returns whether
    they were on before."""
    global _ON, _annotation
    was = _ON
    if on and _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    _ON = bool(on)
    return was
