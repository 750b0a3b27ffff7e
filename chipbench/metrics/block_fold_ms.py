"""User-block fold (``core/useraxis.py`` ``aggregate_block_summaries``):
the segment reduce of a user-blocked grid's rows back to its configs,
enqueued on chip 0. Self time of the program's ``repro.scenario.fold``
spans per grid run, in milliseconds (``_program_spans.py``)."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    return _p.self_ms_per_grid(ctx, "repro.scenario.fold")
