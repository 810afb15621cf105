"""Host spans of the serving path, named ``repro.<layer>.<phase>``.

Each span is a ``jax.profiler.TraceAnnotation``, so a profiler trace
taken meanwhile holds it on the device trace's clock.  While a recording
is open, every span is also kept in memory as a :class:`Span`, timed
with ``time.monotonic`` (the clock of ``Request``'s stamps) and linked
to the span it ran inside::

    from repro import tracing
    tracing.start()
    ...                         # EdgeCluster.submit / step
    spans = tracing.stop()      # [Span(name, start, end, parent, attrs)]

Off, the default, :func:`span` returns one shared no-op context.  The
recorder serves one thread: the cluster's loop.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax


class Span(NamedTuple):
    name: str
    start: float                 # time.monotonic seconds
    end: Optional[float]         # None while the span is open
    parent: Optional[int]        # index of the enclosing span in the list
    attrs: Dict[str, Any]


class _Recording:
    __slots__ = ("spans", "stack")

    def __init__(self):
        self.spans: List[Span] = []
        self.stack: List[int] = []


_rec: Optional[_Recording] = None


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _Noop()


class _Open:
    __slots__ = ("rec", "name", "attrs", "index", "ann")

    def __init__(self, rec: _Recording, name: str, attrs: Dict[str, Any]):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        rec = self.rec
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.index = len(rec.spans)
        rec.spans.append(Span(self.name, time.monotonic(), None,
                              rec.stack[-1] if rec.stack else None,
                              self.attrs))
        rec.stack.append(self.index)
        return self

    def __exit__(self, *exc):
        rec = self.rec
        rec.spans[self.index] = rec.spans[self.index]._replace(
            end=time.monotonic())
        rec.stack.pop()
        self.ann.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        """Attributes known only at the span's end (the recorded copy;
        the profiler's annotation keeps those given at its start)."""
        self.attrs.update(attrs)


def span(name: str, **attrs):
    """``with span("repro.engine.decode", lanes=n): ...``"""
    rec = _rec
    if rec is None:
        return _NOOP
    return _Open(rec, name, attrs)


def start() -> None:
    """Begin recording (a recording already open is discarded)."""
    global _rec
    _rec = _Recording()


def stop() -> List[Span]:
    """End recording; the spans, in the order they opened."""
    global _rec
    rec, _rec = _rec, None
    return rec.spans if rec is not None else []


@contextlib.contextmanager
def paused():
    """Open no spans inside (work that is not serving, such as a
    warm-up call)."""
    global _rec
    rec, _rec = _rec, None
    try:
        yield
    finally:
        _rec = rec
