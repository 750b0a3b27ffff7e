"""Reduce a profiler trace to what the per-layer metrics read.

A JAX profiler trace (``*.xplane.pb``) holds one plane per device
(``/device:TPU:<n>``) with a line of whole programs (``XLA Modules``) and
a line of their operations (``XLA Ops``), and a host plane
(``/host:CPU``) whose lines hold the host's events, among them the
benchmark's ``TraceAnnotation`` spans (named ``cb.<span>``) and the
compiler's ``PJRT_Client_Compile``. All share one clock.

From it this module takes, within the benchmark's ``cb.window`` span:

* per device: busy time (the union of program intervals), device time
  per program and per operation;
* the idle gaps of each device, each piece attributed to the innermost
  host span around it (``compile`` for the compiler), or to
  ``outside spans``.

:func:`breakdown` turns that into the traced run's ``breakdown``.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

from chipbench.spans import SPAN_PREFIX

WINDOW_SPAN = "window"
COMPILE_EVENTS = {"PJRT_Client_Compile": "compile"}
OUTSIDE = "outside spans"
_HASH = re.compile(r"\(\d+\)$")


@dataclass
class DeviceTrace:
    name: str
    busy_s: float = 0.0
    #: program base name (``jit_f``) -> [count, seconds]
    modules: dict = field(default_factory=dict)
    #: full operation text -> [count, seconds, program base name]
    ops: dict = field(default_factory=dict)
    #: host span name -> idle seconds of this device under it
    idle_by_span: dict = field(default_factory=dict)


@dataclass
class Reduced:
    window_s: float
    devices: list

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / max(len(self.devices), 1)

    def busiest(self) -> DeviceTrace:
        return max(self.devices, key=lambda d: d.busy_s)


def find_trace(log_dir) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(log_dir):
    import jax

    return jax.profiler.ProfileData.from_file(str(find_trace(log_dir)))


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi) -> list:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi)``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap_list, spans) -> dict:
    """Seconds of ``gap_list`` under each host span, piece by piece: a
    piece goes to the innermost (latest-started) span active over it."""
    points = []
    for i, (_, s, e) in enumerate(spans):
        points.append((s, 1, i))
        points.append((e, 0, i))
    for s, e in gap_list:
        points.append((s, 3, -1))
        points.append((e, 2, -1))
    points.sort(key=lambda p: (p[0], p[1]))
    active: dict = {}
    in_gap = 0
    out: dict = defaultdict(float)
    last = None
    for t, kind, i in points:
        if last is not None and in_gap and t > last:
            if active:
                j = max(active, key=lambda k: (spans[k][1], k))
                out[spans[j][0]] += t - last
            else:
                out[OUTSIDE] += t - last
        last = t
        if kind == 1:
            active[i] = True
        elif kind == 0:
            active.pop(i, None)
        elif kind == 3:
            in_gap += 1
        else:
            in_gap -= 1
    return dict(out)


def _host_spans(planes) -> list:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    spans.append((name[len(SPAN_PREFIX):],
                                  ev.start_ns * 1e-9, ev.end_ns * 1e-9))
                elif name in COMPILE_EVENTS:
                    spans.append((COMPILE_EVENTS[name], ev.start_ns * 1e-9,
                                  ev.end_ns * 1e-9))
    return spans


def _device(plane, lo, hi, spans) -> DeviceTrace:
    dev = DeviceTrace(plane.name)
    lines = {line.name: line for line in plane.lines}
    mods = []
    if "XLA Modules" in lines:
        for ev in lines["XLA Modules"].events:
            s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
            if e <= lo or s >= hi:
                continue
            base = _HASH.sub("", ev.name)
            mods.append((s, e, base))
            c = dev.modules.setdefault(base, [0, 0.0])
            c[0] += 1
            c[1] += min(e, hi) - max(s, lo)
    mods.sort()
    starts = [m[0] for m in mods]
    op_iv = []
    if "XLA Ops" in lines:
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns * 1e-9, ev.end_ns * 1e-9
            if e <= lo or s >= hi:
                continue
            k = bisect.bisect_right(starts, s) - 1
            base = mods[k][2] if k >= 0 and mods[k][1] >= e else ""
            c = dev.ops.setdefault(ev.name, [0, 0.0, base])
            c[0] += 1
            c[1] += min(e, hi) - max(s, lo)
            op_iv.append((s, e))
    busy = union(_clip([(s, e) for s, e, _ in mods] or op_iv, lo, hi))
    dev.busy_s = sum(e - s for s, e in busy)
    dev.idle_by_span = attribute(gaps(busy, lo, hi), spans)
    return dev


def reduce(pdata) -> Reduced:
    """The reduction of one trace, limited to its ``cb.window`` span."""
    planes = list(pdata.planes)
    spans = _host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {SPAN_PREFIX}{WINDOW_SPAN} span")
    lo, hi = windows[0][0], windows[-1][1]
    inner = [(n, s, e) for n, s, e in spans
             if n != WINDOW_SPAN and e > lo and s < hi]
    devices = [_device(p, lo, hi, inner) for p in planes
               if re.fullmatch(r"/device:[A-Z]+:\d+", p.name)]
    return Reduced(window_s=hi - lo, devices=devices)


def _short_op(text: str) -> str:
    return text.split(" = ", 1)[0].strip()


def breakdown(red: Reduced, top: int = 10) -> dict:
    """``{"device_ops": [[name, seconds]], "idle_gaps": [[name,
    seconds]]}``: the operations that took the most device time
    (``program/op``, averaged over devices) and the device's idle time
    by the host span it fell under (averaged over devices)."""
    n = max(len(red.devices), 1)
    ops: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    for d in red.devices:
        for text, (_c, sec, base) in d.ops.items():
            ops[f"{base}/{_short_op(text)}" if base else _short_op(text)] \
                += sec / n
        for name, sec in d.idle_by_span.items():
            idle[name] += sec / n
    def rank(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}
