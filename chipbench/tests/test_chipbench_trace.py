"""Trace reduction on a small recorded trace: busy/idle union, kernel
time, idle-gap attribution to host spans, and the breakdown."""

from __future__ import annotations

from dataclasses import dataclass, field

import pytest
from chipbench_testkit import REPO

from chipbench import bench, trace_reduce

KERNEL_OP = ('%_pallas_hoisted_route.1 = (s32[4096,1], f32[1,1024]) '
             'custom-call(f32[5,1024] %a), custom_call_target='
             '"tpu_custom_call"')


def metric(name):
    return bench.load_module(REPO / "chipbench" / "metrics" / f"{name}.py")


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns


@dataclass
class Line:
    name: str
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: list = field(default_factory=list)


@dataclass
class Trace:
    planes: list


def recorded(ms=1_000_000):
    """Window 0-100 ms. Device busy 10-20 (route: kernel 12-18) and
    15-30 overlapping, 60-70. Host: plane.run 5-95 holding
    observe 30-55 (with a compile 35-50) and route 55-75."""
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit__route_fused(123)", 10 * ms, 10 * ms),
                             Ev("jit__obs_counts(9)", 15 * ms, 15 * ms),
                             Ev("jit__route_fused(123)", 60 * ms, 10 * ms)]),
        Line("XLA Ops", [Ev(KERNEL_OP, 12 * ms, 6 * ms),
                         Ev("%fusion = s32[9] fusion(%x)", 16 * ms, 2 * ms),
                         Ev(KERNEL_OP, 61 * ms, 8 * ms)])])
    host = Plane("/host:CPU", [
        Line("python", [Ev("cb.window", 0, 100 * ms),
                        Ev("cb.plane.run", 5 * ms, 90 * ms),
                        Ev("cb.gateway.observe_detections_window",
                           30 * ms, 25 * ms),
                        Ev("cb.gateway.route_window", 55 * ms, 20 * ms),
                        Ev("unrelated", 0, 1 * ms)]),
        Line("main/1", [Ev("PJRT_Client_Compile", 35 * ms, 15 * ms)])])
    return Trace([Plane("/host:metadata"), dev,
                  Plane("/device:CUSTOM:Megascale Trace"), host])


def test_union_and_gaps():
    assert trace_reduce.union([(3, 4), (0, 2), (1, 2.5), (4, 5)]) == \
        [(0, 2.5), (3, 5)]
    assert trace_reduce.gaps([(1, 2), (3, 4)], 0, 5) == \
        [(0, 1), (2, 3), (4, 5)]


def test_reduce_busy_idle_and_attribution():
    red = trace_reduce.reduce(recorded())
    assert red.window_s == pytest.approx(0.1)
    (dev,) = red.devices
    assert dev.busy_s == pytest.approx(0.030)        # 10-30 and 60-70
    assert red.busy_s == pytest.approx(0.030)
    assert dev.modules["jit__route_fused"][0] == 2
    idle = dev.idle_by_span
    assert sum(idle.values()) == pytest.approx(0.070)
    assert idle["outside spans"] == pytest.approx(0.010)   # 0-5, 95-100
    assert idle["plane.run"] == pytest.approx(0.005 + 0.020)  # 5-10, 75-95
    assert idle["gateway.observe_detections_window"] == pytest.approx(
        0.010)                                           # 30-35, 50-55
    assert idle["compile"] == pytest.approx(0.015)
    assert idle["gateway.route_window"] == pytest.approx(0.010)  # 55-60, 70-75


def test_kernel_time_and_breakdown():
    red = trace_reduce.reduce(recorded())
    k = metric("_kernel")
    calls, sec = k.kernel_time(red)
    assert calls == 2 and sec == pytest.approx(0.014)
    ctx = {"reduced": red, "window": 4096, "decisions": 8192,
           "n_groups": 5, "n_pairs": 1024, "device_kind": "TPU v5 lite"}
    us = metric("moscore_us_per_decision").read(ctx)
    assert us == pytest.approx(1e6 * 0.014 / 8192)
    idle = metric("device_idle_pct.serve").read(ctx)
    assert idle == pytest.approx(70.0)
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0] == ["jit__route_fused/%_pallas_hoisted_route.1",
                                   pytest.approx(0.014)]
    assert bd["idle_gaps"][0] == ["plane.run", pytest.approx(0.025)]
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10


def test_reduce_needs_the_window_span():
    t = recorded()
    t.planes[3].lines[0].events = t.planes[3].lines[0].events[1:]
    with pytest.raises(ValueError):
        trace_reduce.reduce(t)


def test_no_kernel_reads_nothing():
    t = recorded()
    t.planes[1].lines[1].events = t.planes[1].lines[1].events[1:2]
    red = trace_reduce.reduce(t)
    ctx = {"reduced": red, "window": 4096, "decisions": 8192,
           "n_groups": 5, "n_pairs": 1024, "device_kind": "TPU v5 lite"}
    for name in ("moscore_us_per_decision", "moscore_roofline_pct"):
        assert metric(name).read(ctx) is None


def test_sweep_metrics_read_the_busiest_device():
    ms = 1_000_000
    t = recorded()
    quiet = Plane("/device:TPU:1", [Line("XLA Modules", [
        Ev("jit_fn(5)", 40 * ms, 5 * ms)])])
    t.planes.insert(2, quiet)
    red = trace_reduce.reduce(t)
    assert [d.name for d in red.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert red.busy_s == pytest.approx((0.030 + 0.005) / 2)
    ctx = {"reduced": red, "grid_runs": 2}
    # the program with the most device time on the busiest device
    assert metric("sweep_scan_ms").read(ctx) == pytest.approx(1e3 * 0.020 / 2)
    assert metric("device_idle_pct.sweep").read(ctx) == pytest.approx(70.0)
    assert metric("sweep_scan_ms").read({"reduced": red}) is None
