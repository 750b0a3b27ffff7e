"""Peaks of the chips the benchmark runs on, and the operation and byte
counts of the kernels whose roofline share it reports.

Peaks live in ``peaks.json`` keyed by the ``device_kind`` JAX reports; a
device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s", ...}`` of one chip of
    ``device_kind``; raises ``KeyError`` for a device not in the table."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def lane_pad(n_pairs: int) -> int:
    """P': the pair axis padded to whole 128-lane vregs, as the routing
    kernel lays it out."""
    return (n_pairs + 127) // 128 * 128


def moscore_hoisted_cost(window: int, n_groups: int, n_pairs: int):
    """``(ops, bytes)`` one call of the invariant-hoisted routing kernel
    needs: ``window`` sequential Algorithm-1 steps over ``P'`` lanes.

    Per step and lane: L = T*(1+q) (2), two masked min/max selects and
    reductions (4), the normalisation (subtract, subtract, max, divide:
    4), the weighted sum and energy term (3), the feasibility select (1),
    the first-minimum rule (min, compare, select, min: 4) and the queue
    bump (compare, add: 2) -- 20 operations. Bytes are what must cross
    HBM once: the three (G, P') float32 tables and the (1, P') queue in,
    the (1, P') queue out, and the (W, 1) groups in and decisions out,
    as int32."""
    p = lane_pad(n_pairs)
    ops = 20 * window * p
    nbytes = 4 * (3 * n_groups * p + 2 * p + 2 * window)
    return ops, nbytes


def roofline_pct(ops: float, nbytes: float, seconds: float,
                 device_kind: str):
    """``(share_pct, bound)``: the least time the chip could take for
    ``ops`` and ``nbytes`` (the larger of the compute and memory bound)
    over the time measured, in percent, and which bound it was."""
    pk = peaks(device_kind)
    t_flops = ops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
