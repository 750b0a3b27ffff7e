"""Routing kernel (``kernels/moscore/moscore.py``
``_moscore_hoisted_kernel``): its device time per routed decision, from
the trace."""

from pathlib import Path

from chipbench.bench import load_module

_k = load_module(Path(__file__).with_name("_kernel.py"))


def read(ctx):
    calls, sec = _k.kernel_time(ctx["reduced"])
    if not calls or not ctx.get("decisions"):
        return None
    return 1e6 * sec / (calls * ctx["window"])
