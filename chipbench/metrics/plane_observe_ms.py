"""Estimator scatter and belief fold (``serving/engine.py``
``ServingPlane._observe`` -> ``serving/gateway.py``
``observe_detections_window`` / ``_obs_counts`` and, under online
dispatch, ``observe_window`` / ``_observe_win``), programs they compile
inside the window included. Self time of the program's
``repro.plane.observe`` spans per admission window, in milliseconds
(``_program_spans.py``)."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    return _p.self_ms_per(ctx, "repro.plane.observe", "windows")
