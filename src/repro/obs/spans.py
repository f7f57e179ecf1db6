"""Host spans on the served path, off by default.

    from repro.obs import spans
    with spans.span("repro.engine.decode", n=len(ids)):
        ...

While disabled, ``span`` returns one shared no-op context manager: the
cost is one module-global ``None`` check.  ``enable(annotate)`` installs
the factory that opens a span.  With ``jax.profiler.TraceAnnotation``
as the factory, every span lands on the profiler's host plane and
clock, beside the device's operations (docs/operations.md, "Tracing").
This module stays sealed (stdlib only, no JAX): the factory is
injected.

While enabled, each garbage collection is recorded as the span
``repro.host.gc`` (``gen`` = the generation collected).
"""

from __future__ import annotations

import gc
from typing import Callable, Optional

GC_SPAN = "repro.host.gc"


class _Noop:
    """The shared do-nothing span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP = _Noop()

_annotate: Optional[Callable] = None
_gc_open: list = []       # the collection in progress (at most one)


def span(name: str, **meta):
    """A context manager around the host work named ``name``; ``meta``
    becomes the span's stats (``rid``, ``n``)."""
    if _annotate is None:
        return NOOP
    return _annotate(name, **meta)


def enable(annotate: Callable) -> None:
    """Open every span with ``annotate(name, **meta)``, a context-manager
    factory, and record garbage collections."""
    global _annotate
    _annotate = annotate
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def disable() -> None:
    """Back to the no-op span; stop recording collections."""
    global _annotate
    _annotate = None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    _gc_open.clear()


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _annotate is not None:
            s = _annotate(GC_SPAN, gen=info.get("generation", -1))
            s.__enter__()
            _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)
