"""Spans and counters the benchmark records around the program's own
methods, without touching the program.

:class:`Recorder` wraps named methods of live objects. Every call is
logged with its arguments and result (the correctness replay reads the
log); in a traced run each call is also a host-clock span and a
``jax.profiler.TraceAnnotation`` of the same name, so host spans and
device events share the profiler's clock. :class:`CompileCounter` counts
the programs JAX builds, whether compiled or loaded from the persistent
cache.
"""

from __future__ import annotations

import time

SPAN_PREFIX = "cb."
#: the event JAX records around every backend compile or cache load
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Recorder:
    """Call log and spans of wrapped methods.

    ``log`` holds ``(name, args, kwargs, result)`` per call in call
    order; ``spans`` holds ``(name, start_s, end_s)`` on
    ``time.perf_counter`` while :attr:`timing` is on."""

    def __init__(self):
        self.log: list = []
        self.spans: list = []
        self.timing = False

    def wrap(self, obj, label: str, methods) -> None:
        for m in methods:
            setattr(obj, m, self._wrapped(f"{label}.{m}", getattr(obj, m)))

    def _wrapped(self, name: str, fn):
        import jax

        span_name = SPAN_PREFIX + name

        def call(*args, **kwargs):
            if not self.timing:
                out = fn(*args, **kwargs)
                self.log.append((name, args, kwargs, out))
                return out
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(span_name):
                out = fn(*args, **kwargs)
            self.spans.append((name, t0, time.perf_counter()))
            self.log.append((name, args, kwargs, out))
            return out

        return call

    def span(self, name: str):
        """A span of the benchmark's own (a window, one call of the
        entry point), recorded like a wrapped call's."""
        return _Span(self, name)

    def span_seconds(self, names) -> float:
        names = set(names)
        return sum(t1 - t0 for n, t0, t1 in self.spans if n in names)


class _Span:
    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        import jax

        self.ann = None
        if self.rec.timing:
            self.ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
            self.rec.spans.append((self.name, self.t0, t1))
        return False


class CompileCounter:
    """Times at which JAX finished building a program (compiled, or
    loaded from the persistent cache), from a ``jax.monitoring``
    listener. A listener cannot be removed, so make one per process."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)
