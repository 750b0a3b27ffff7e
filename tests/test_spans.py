"""The program's spans and counters (``repro.common.spans``): silent
without a profiler, nested on ``perf_counter`` and on the trace's host
plane with one, and placed at the layer boundaries of the sweep engine
and the serving plane. Also the serving plane's scene draw when a
transition row's cumulative sum ends below 1."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.common import spans
from repro.core.scenario import Scenario, Sweep, run
from repro.serving.engine import ServingPlane

REPO = Path(__file__).resolve().parents[1]
SWEEP_CHILDREN = {"repro.scenario.build_grid", "repro.scenario.launch",
                  "repro.scenario.fetch"}
PLANE_CHILDREN = {"repro.plane.poll", "repro.plane.observe",
                  "repro.plane.admit", "repro.plane.route",
                  "repro.plane.submit"}


@pytest.fixture
def profiling(tmp_path):
    """A JAX profiler session around the test; yields the trace dir."""
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()


def _spans():
    return [r for r in spans.records() if isinstance(r, spans.Span)]


def _counts():
    return [r for r in spans.records() if isinstance(r, spans.Count)]


def test_nothing_recorded_without_a_profiler(monkeypatch):
    def no_annotation(*a, **k):
        raise AssertionError("annotation opened without a profiler")

    monkeypatch.setattr(spans, "TraceAnnotation", no_annotation)
    spans.clear()
    assert not spans.collecting()
    with spans.span("repro.test.outer", window=1):
        with spans.timed("repro.test.inner") as inner:
            spans.count("test.count", 3)
    assert inner.seconds >= 0.0
    run(Scenario(n_requests=60), Sweep(policy=("MO", "HA")))
    ServingPlane.build(Scenario(n_users=6, seed=1), window=16).run(48)
    assert spans.records() == []


def test_spans_nest_on_perf_counter(profiling):
    with spans.span("repro.test.outer", window=4):
        with spans.timed("repro.test.inner", rid0=9) as inner:
            spans.count("test.count", 3)
        spans.count("test.gauge", 7, fn="f")
    outer_r, = [r for r in _spans() if r.name == "repro.test.outer"]
    inner_r, = [r for r in _spans() if r.name == "repro.test.inner"]
    assert outer_r.parent_id is None
    assert inner_r.parent_id == outer_r.span_id
    assert outer_r.t0 <= inner_r.t0 <= inner_r.t1 <= outer_r.t1
    assert inner_r.t1 - inner_r.t0 == inner.seconds
    assert outer_r.ids == {"window": 4}
    assert inner_r.ids == {"window": 4, "rid0": 9}
    c3, c7 = _counts()
    assert (c3.name, c3.n, c3.parent_id) == ("test.count", 3,
                                             inner_r.span_id)
    assert (c7.name, c7.n, c7.parent_id) == ("test.gauge", 7,
                                             outer_r.span_id)
    assert c7.ids == {"window": 4, "fn": "f"}
    assert inner_r.t0 <= c3.t <= inner_r.t1
    spans.clear()
    assert spans.records() == []


def test_span_names_on_the_host_plane(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("repro.test.host", window=2):
            spans.count("test.host_count", 5)
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    pdata = jax.profiler.ProfileData.from_file(str(path))
    events = {ev.name: dict(ev.stats) for plane in pdata.planes
              if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(("repro.", "test."))}
    assert events["repro.test.host"] == {"window": 2}
    assert events["test.host_count"] == {"window": 2, "n": 5}


def test_scenario_run_spans(profiling):
    run(Scenario(n_requests=60), Sweep(policy=("MO", "HA")))
    recs = _spans()
    top, = [r for r in recs if r.name == "repro.scenario.run"]
    kids = [r for r in recs if r.parent_id == top.span_id]
    assert {r.name for r in kids} == SWEEP_CHILDREN
    assert all(top.t0 <= r.t0 <= r.t1 <= top.t1 for r in kids)


_FORCED_4 = """
import json, sys
import jax
from repro.common import spans
from repro.core.scenario import Scenario, Sweep, run
sc = Scenario(n_users=23, user_block=5, n_requests=60, mesh="local")
sw = Sweep(policy=("MO", "HA"))
res = run(sc, sw)
jax.profiler.start_trace(sys.argv[1])
try:
    run(sc, sw)
finally:
    jax.profiler.stop_trace()
print(json.dumps({"devices": jax.device_count(), "spans": [
    [r.name, r.span_id, r.parent_id] for r in spans.records()
    if isinstance(r, spans.Span)], "counts": [
    [r.name, r.parent_id, r.n] for r in spans.records()
    if isinstance(r, spans.Count)], "metrics": len(res.metric_names)}))
"""


def test_user_blocked_sharded_run_spans(tmp_path):
    """On 4 forced CPU devices a user-blocked sweep also folds, and the
    gather onto one device sits inside the launch span and counts the
    bytes it moves once: the scalar leaves and the per-config histogram
    merged on the shards, no per-row histogram."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", _FORCED_4, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    by_id = {i: (n, p) for n, i, p in out["spans"]}
    names = {n for n, _ in by_id.values()}
    assert names == SWEEP_CHILDREN | {"repro.scenario.run",
                                      "repro.scenario.fold",
                                      "repro.sweep.gather"}
    for n, p in by_id.values():
        if n == "repro.sweep.gather":
            assert by_id[p][0] == "repro.scenario.launch"
        elif n == "repro.scenario.fold":
            assert by_id[p][0] == "repro.scenario.run"
    # one gather, of the rows' scalar metrics and the histogram merged
    # per config on the shards: 2 configs of 5 blocks, padded to 12 rows
    (name, parent, nbytes), = out["counts"]
    assert name == "sweep.gather_bytes"
    assert by_id[parent][0] == "repro.sweep.gather"
    hist = 2 * 4096 * 4
    assert hist <= nbytes <= 12 * out["metrics"] * 4 + 4 * hist


def test_serving_plane_window_spans(profiling):
    plane = ServingPlane.build(Scenario(n_users=12, seed=3), window=32)
    recs = plane.run(200)
    spans_ = _spans()
    windows = {r.span_id: r for r in spans_
               if r.name == "repro.plane.window"}
    assert [w.ids["window"] for w in windows.values()] == \
        list(range(len(recs["router_window_s"])))
    assert [w.ids["rid0"] for w in windows.values()] == \
        list(range(0, 200, 32))
    kids = [r for r in spans_ if r.parent_id in windows]
    assert {r.name for r in kids} == PLANE_CHILDREN
    for r in kids:
        assert r.ids["window"] == windows[r.parent_id].ids["window"]
    # the route span and the router's sample are one pair of clock reads
    route = [r.t1 - r.t0 for r in kids if r.name == "repro.plane.route"]
    np.testing.assert_array_equal(route, recs["router_window_s"])
    counts = _counts()
    admitted = [c for c in counts if c.name == "plane.admitted"]
    assert sum(c.n for c in admitted) == 200
    assert [c.parent_id for c in admitted] == list(windows)
    assert sum(c.n for c in counts if c.name == "plane.retried") == 0
    in_flight = [c.n for c in counts if c.name == "pool.in_flight"]
    assert len(in_flight) == len(windows) and min(in_flight) > 0
    retraced = {c.ids["fn"] for c in counts if c.name == "gateway.retrace"}
    assert {"_route_fused", "_obs_counts"} <= retraced


def test_serving_plane_fault_spans(profiling):
    from repro.core.faults import FaultSchedule

    fs = FaultSchedule(outages=((3, 40, 160),), timeout_ms=400.0,
                       max_attempts=2)
    plane = ServingPlane.build(Scenario(n_users=12, n_requests=0, seed=3,
                                        faults=fs),
                               window=16, offered_rps=30.0)
    recs = plane.run(240)
    names = [r.name for r in _spans()]
    assert names.count("repro.plane.faults") \
        == names.count("repro.plane.window")
    counts = _counts()
    assert sum(c.n for c in counts if c.name == "plane.retried") \
        == recs["retried"] > 0
    assert sum(c.n for c in counts if c.name == "plane.admitted") == 240


class _HighDraws:
    """A generator whose uniforms sit just below 1."""

    def __init__(self, rng):
        self._rng = rng

    def random(self, size=None):
        return np.full(size, 1.0 - 2.0 ** -26)

    def __getattr__(self, name):
        return getattr(self._rng, name)


def test_scene_draw_stays_in_range_when_a_row_ends_below_one(monkeypatch):
    """A transition matrix from the device can have rows whose float32
    cumulative sum ends below 1; a uniform above that end must still
    step to a group in range."""
    from repro.serving import engine

    real = engine.EST.markov_transition

    def short_rows(G, stickiness):
        P = np.array(real(G, stickiness), np.float32)
        P[:, -1] -= np.float32(1e-6)
        return P

    monkeypatch.setattr(engine.EST, "markov_transition", short_rows)
    assert (short_rows(5, 0.85).cumsum(axis=1)[:, -1] < 1.0 - 2.0 ** -26
            ).all()
    plane = ServingPlane.build(Scenario(n_users=12, seed=3), window=16)
    plane.run(16)
    plane._rng = _HighDraws(plane._rng)
    recs = plane.run(64)
    G = plane.gateway.prof.n_groups
    assert recs["g_true"].size == 64
    assert recs["g_true"].max() == G - 1
    assert (plane._scene < G).all()
