"""The program's spans and counters, recorded only while a JAX profiler
session is collecting.

``span(name, **ids)`` marks a region of host code. While a profile is
being captured (``jax.profiler.start_trace`` ... ``stop_trace``, or a
remote capture of ``jax.profiler.start_server``) it opens a
``jax.profiler.TraceAnnotation`` of the same name, with ``ids`` as the
event's stats, so the region sits on the host plane of the device trace,
on the trace's clock; and on exit it appends a :class:`Span` on
``time.perf_counter`` to an in-memory buffer of fixed size. Otherwise it
costs one check. A span's parent is the span open around it in the same
thread, and its ``ids`` are its parent's updated with its own, so every
span and count inside an admission window carries the window's index.

``count(name, n, **ids)`` records a :class:`Count` under the same gate.
``records()`` reads the buffer and ``clear()`` empties it.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: records kept; the oldest drop first
CAPACITY = 1 << 16


class Span(NamedTuple):
    name: str
    span_id: int
    parent_id: int | None
    t0: float
    t1: float
    ids: dict


class Count(NamedTuple):
    name: str
    parent_id: int | None
    t: float
    n: float
    ids: dict


_buf: deque = deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_NULL = contextlib.nullcontext()
collecting = TraceAnnotation.is_enabled


def _stack() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def _inherit(ids: dict) -> tuple:
    """The id of this thread's innermost open span, and ``ids`` over
    that span's ids."""
    stack = _stack()
    if not stack:
        return None, ids
    return stack[-1].span_id, {**stack[-1].ids, **ids}


class _Span:
    """An open span; ``timed`` spans read the clock even when nothing is
    collecting, for a caller that keeps the duration (``seconds``)."""

    def __init__(self, name: str, ids: dict, on: bool):
        self.name, self.ids, self.on = name, ids, on

    def __enter__(self):
        if self.on:
            self.parent_id, self.ids = _inherit(self.ids)
            self.span_id = next(_ids)
            _stack().append(self)
            self.ann = TraceAnnotation(self.name, **self.ids)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.on:
            self.ann.__exit__(*exc)
            _stack().pop()
            _buf.append(Span(self.name, self.span_id, self.parent_id,
                             self.t0, self.t1, self.ids))
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str, **ids):
    """A span of ``name``; a no-op unless a profile is being captured."""
    return _Span(name, ids, True) if collecting() else _NULL


def timed(name: str, **ids) -> _Span:
    """:func:`span` that always times itself: read ``.seconds`` after."""
    return _Span(name, ids, collecting())


def count(name: str, n: float = 1, **ids) -> None:
    """A counter event of ``n`` (a gauge's value, or an increment)."""
    if not collecting():
        return
    parent_id, ids = _inherit(ids)
    with TraceAnnotation(name, n=n, **ids):
        t = time.perf_counter()
    _buf.append(Count(name, parent_id, t, n, ids))


def records() -> list:
    """The recorded :class:`Span` and :class:`Count` events, oldest
    first (a span is recorded when it closes)."""
    return list(_buf)


def clear() -> None:
    _buf.clear()
