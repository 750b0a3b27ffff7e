"""Routing decision's host side (``serving/gateway.py`` ``route_window``:
the host-to-device copy of the queue depths, the routing program's
enqueue and the wait until its decisions are on the host), beside the
kernel's device time in ``moscore_us_per_decision``. Self time of the
program's ``repro.plane.route`` spans per admission window, in
milliseconds (``_program_spans.py``)."""

from pathlib import Path

from chipbench.bench import load_module

_p = load_module(Path(__file__).with_name("_program_spans.py"))


def read(ctx):
    return _p.self_ms_per(ctx, "repro.plane.route", "windows")
