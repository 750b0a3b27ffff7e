"""Result fetch (``core/scenario.py`` ``run``): the per-metric copy to
the host and the final transpose, where the host waits for the device
to finish the grid. Self time of the program's
``repro.scenario.fetch`` spans per grid run, in milliseconds
(``_program_spans.py``)."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    return _p.self_ms_per_grid(ctx, "repro.scenario.fetch")
