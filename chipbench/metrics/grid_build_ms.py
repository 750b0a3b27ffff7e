"""Grid build (``core/scenario.py`` ``run`` -> ``simulator._make_grid``
or ``_make_user_grid``): the host packs the config grid, draws each
row's initial state and uploads it. Self time of the program's
``repro.scenario.build_grid`` spans per grid run, in milliseconds
(``_program_spans.py``)."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    return _p.self_ms_per(ctx, "repro.scenario.build_grid", "grid_runs")
