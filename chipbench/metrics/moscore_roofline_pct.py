"""Routing kernel's share of its roofline: the least time the chip could
take for the kernel's operations and bytes (``chipbench.roofline
.moscore_hoisted_cost`` of (W, G, P')), over its device time in the
trace. The kernel is a W-step serial recurrence, so the share is
expected to be tiny."""

from pathlib import Path

from chipbench.bench import load_module
from chipbench.roofline import moscore_hoisted_cost, roofline_pct

_k = load_module(Path(__file__).with_name("_kernel.py"))


def read(ctx):
    calls, sec = _k.kernel_time(ctx["reduced"])
    if not calls or sec <= 0:
        return None
    ops, nbytes = moscore_hoisted_cost(ctx["window"], ctx["n_groups"],
                                       ctx["n_pairs"])
    pct, _bound = roofline_pct(calls * ops, calls * nbytes, sec,
                               ctx["device_kind"])
    return pct
