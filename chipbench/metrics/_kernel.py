"""Device time of the hoisted ``moscore`` routing kernel in a trace.

The kernel is a Mosaic custom call inside the gateway's routing program;
a TPU trace names the operation after the jitted wrapper that issues it,
``%_pallas_hoisted_route[.n] = ... custom-call(...)`` with
``custom_call_target="tpu_custom_call"``."""

import re

KERNEL = re.compile(r"^%_pallas_hoisted_route[.\d]* = .*tpu_custom_call")


def kernel_time(red):
    """``(calls, seconds)`` of the kernel, summed over devices."""
    calls, sec = 0, 0.0
    for d in red.devices:
        for text, (n, s, _base) in d.ops.items():
            if KERNEL.match(text):
                calls += n
                sec += s
    return calls, sec
