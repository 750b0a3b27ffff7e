"""Sweep engine (``core/scenario.py`` ``run`` -> the fused simulator
program of ``core/simulator.py``): device time per grid run of the
program that takes the most device time, on the busiest device, from
the trace."""


def read(ctx):
    red = ctx["reduced"]
    if not red.devices or not ctx.get("grid_runs"):
        return None
    dev = red.busiest()
    if not dev.modules:
        return None
    _n, sec = max(dev.modules.values(), key=lambda c: c[1])
    return 1e3 * sec / ctx["grid_runs"]
