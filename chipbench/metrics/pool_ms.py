"""Executor pool (``serving/executor.py``): host time of
``AsyncExecutorPool.poll`` and ``submit_window`` per admission window,
from the benchmark's spans around them (neither calls another wrapped
method, so a span's time is its self time)."""


def read(ctx):
    names = ctx.get("pool_spans")
    if not names or not ctx.get("windows"):
        return None
    return 1e3 * ctx["recorder"].span_seconds(names) / ctx["windows"]
