"""Launch (``core/simulator.py`` ``_sweep_summaries``): padding to the
mesh, the enqueue of the fused or sharded program and, on the sharded
path, the gather onto chip 0 (``repro.sweep.gather``, counted here).
Self time of the program's ``repro.scenario.launch`` spans per grid
run, in milliseconds (``_program_spans.py``)."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    return _p.self_ms_per_grid(ctx, "repro.scenario.launch",
                              own=("repro.sweep.gather",))
