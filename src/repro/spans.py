"""Spans inside the diagnosis tick, on the JAX profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands on the host plane
(``/host:CPU``) of the profile the device's ops are written to, on the
same clock, so a trace shows what the host was doing in every gap of the
device.  The span names are listed in ``docs/operations.md`` ("Tracing
the aggregator").

While spans are off, :func:`span` returns one shared ``nullcontext``.
:func:`enable` switches them on or off; until it is called they follow
the profiler: each :func:`root` span (one per ingest, one per sweep)
turns them on while a JAX profiler trace is being collected in this
process and off once it has stopped.  Nothing here imports JAX unless
:func:`enable` switches spans on: following reads only a JAX the process
has already imported, so producers and decoders that never touch JAX
stay free of it.
"""
from __future__ import annotations

import sys
from contextlib import nullcontext

_NULL = nullcontext()
#: True/False once enable() was called; None follows the profiler.
_forced: bool | None = None
#: The annotation class while spans are on, else None.
_annotation = None


def enable(on: bool | None = True) -> None:
    """Switch spans on (``True``) or off (``False``); ``None`` lets them
    follow the profiler again from the next root span."""
    global _forced, _annotation
    _forced = on
    if on:
        import jax.profiler

        _annotation = jax.profiler.TraceAnnotation
    else:
        _annotation = None


def span(name: str, **args):
    """A context manager timing its block as span ``name``; ``args``
    become the event's arguments (e.g. ``tick=``)."""
    return _NULL if _annotation is None else _annotation(name, **args)


def root(name: str, **args):
    """:func:`span` for an outermost span, which first brings spans in
    line with the profiler unless :func:`enable` fixed them."""
    global _annotation
    if _forced is None:
        profiler = sys.modules.get("jax.profiler")
        cls = None if profiler is None else profiler.TraceAnnotation
        _annotation = cls if cls is not None and cls.is_enabled() else None
    return span(name, **args)
