"""Spans of the query path, on the profiler's clock.

``span(name, **meta)`` is a :class:`jax.profiler.TraceAnnotation` while
tracing is on: its events land in the profiler's trace (the XSpace that
``jax.profiler.start_trace`` writes), on the same clock as the device's
``XLA Ops``. While tracing is off it is one shared no-op context, and
nothing is built. ``enable(True)`` turns tracing on; a caller does so only
while a profiler capture runs, since a span outside one records nothing.

While tracing is on, a ``gc.callbacks`` hook wraps each collection of the
cyclic garbage collector in an ``itr.gc`` span, so a traced window shows
whether a collection stalled it.

Every span name starts with ``itr.``; the names, where each sits and what
reads it are listed in ``docs/ARCHITECTURE.md`` ("Tracing").
"""
from __future__ import annotations

import contextlib
import functools
import gc

_OFF = contextlib.nullcontext()
_make = None      # TraceAnnotation while tracing is on, else None
_gc_span = None   # the open itr.gc span (collections never overlap)


def enabled() -> bool:
    return _make is not None


def enable(on: bool) -> None:
    """Turn tracing on or off (idempotent)."""
    global _make
    if on and _make is None:
        from jax.profiler import TraceAnnotation

        _make = TraceAnnotation
        gc.callbacks.append(_on_gc)
    elif not on and _make is not None:
        _make = None
        gc.callbacks.remove(_on_gc)


def span(name: str, **meta):
    """A context that records `name` (with `meta` as its stats) while
    tracing is on; the shared no-op context while it is off."""
    make = _make
    return _OFF if make is None else make(name, **meta)


def traced(name: str):
    """Decorator: the whole call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = span("itr.gc", generation=info["generation"])
        _gc_span.__enter__()
    elif _gc_span is not None:
        done, _gc_span = _gc_span, None
        done.__exit__(None, None, None)
