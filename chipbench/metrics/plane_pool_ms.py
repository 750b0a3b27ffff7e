"""Executor pool (``serving/executor.py`` ``AsyncExecutorPool.poll`` and
``submit_window``). Self time of the program's ``repro.plane.poll`` and
``repro.plane.submit`` spans per admission window, in milliseconds
(``_program_spans.py``); ``None`` where the program records neither."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    parts = [_p.self_ms_per(ctx, name, "windows")
             for name in ("repro.plane.poll", "repro.plane.submit")]
    parts = [v for v in parts if v is not None]
    return sum(parts) if parts else None
