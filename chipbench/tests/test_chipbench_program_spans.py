"""The per-layer metrics that read the program's own spans: self time per
grid run and per admission window on planted records (children, the
gather counted as the launch's own, the window cut), silence where the
program records nothing, and traced tiny cells, a user-blocked sweep
and a served plane, that report every one of them."""

from __future__ import annotations

import json

import pytest
from chipbench_testkit import REPO, make_checkout, run_cell

from chipbench import bench
from chipbench.spans import Recorder
from repro.common import spans

READERS = ("grid_build_ms", "sweep_launch_ms", "block_fold_ms",
           "result_wait_ms")
PLANE_READERS = ("plane_observe_ms", "plane_pool_ms", "plane_route_ms")
PHASES = ("repro.scenario.build_grid", "repro.scenario.launch",
          "repro.scenario.fold", "repro.scenario.fetch")


def metric(name):
    return bench.load_module(REPO / "chipbench" / "metrics" / f"{name}.py")


def planted():
    """Window 1.0-3.0 s. Grid A, wholly inside: run 1.0-2.0 holding
    build 1.0-1.2, launch 1.2-1.5 (gather 1.3-1.4), fold 1.5-1.6 and
    fetch 1.6-1.95. Grid B, cut by the window's end: run 2.0-3.5 holding
    build 2.0-2.1, launch 2.1-2.3, fetch 2.3-3.4 and fetch 3.4-3.5. A
    build before the window, 0.5-0.9, is left out."""
    S = spans.Span
    return [
        S("repro.scenario.build_grid", 1, 0, 0.5, 0.9, {}),
        S("repro.scenario.build_grid", 11, 10, 1.0, 1.2, {}),
        S("repro.sweep.gather", 13, 12, 1.3, 1.4, {}),
        S("repro.scenario.launch", 12, 10, 1.2, 1.5, {}),
        S("repro.scenario.fold", 14, 10, 1.5, 1.6, {}),
        S("repro.scenario.fetch", 15, 10, 1.6, 1.95, {}),
        spans.Count("plane.admitted", 10, 1.9, 4, {}),
        S("repro.scenario.run", 10, None, 1.0, 2.0, {}),
        S("repro.scenario.build_grid", 21, 20, 2.0, 2.1, {}),
        S("repro.scenario.launch", 22, 20, 2.1, 2.3, {}),
        S("repro.scenario.fetch", 23, 20, 2.3, 3.4, {}),
        S("repro.scenario.fetch", 24, 20, 3.4, 3.5, {}),
        S("repro.scenario.run", 20, None, 2.0, 3.5, {}),
    ]


def planted_plane():
    """Window 1.0-3.0 s. Admission window A, wholly inside: 1.0-1.5
    holding poll 1.0-1.05, observe 1.05-1.25, admit 1.25-1.3, route
    1.3-1.45 and submit 1.45-1.5. Window B, cut by the window's end:
    2.9-3.2 holding poll 2.9-2.95, observe 2.95-3.1 and route 3.1-3.2.
    An observe before the window, 0.5-0.6, is left out."""
    S = spans.Span
    return [
        S("repro.plane.observe", 2, 1, 0.5, 0.6, {}),
        S("repro.plane.window", 1, None, 0.4, 0.7, {}),
        S("repro.plane.poll", 11, 10, 1.0, 1.05, {}),
        S("repro.plane.observe", 12, 10, 1.05, 1.25, {}),
        S("repro.plane.admit", 13, 10, 1.25, 1.3, {}),
        S("repro.plane.route", 14, 10, 1.3, 1.45, {}),
        S("repro.plane.submit", 15, 10, 1.45, 1.5, {}),
        spans.Count("plane.admitted", 10, 1.5, 4, {}),
        S("repro.plane.window", 10, None, 1.0, 1.5, {}),
        S("repro.plane.poll", 21, 20, 2.9, 2.95, {}),
        S("repro.plane.observe", 22, 20, 2.95, 3.1, {}),
        S("repro.plane.route", 23, 20, 3.1, 3.2, {}),
        S("repro.plane.window", 20, None, 2.9, 3.2, {}),
    ]


def planted_ctx(grid_runs=2, windows=None):
    rec = Recorder()
    rec.spans.append(("window", 1.0, 3.0))
    return {"recorder": rec, "grid_runs": grid_runs, "windows": windows}


def test_readers_on_planted_spans(monkeypatch):
    monkeypatch.setattr(spans, "records", planted)
    ctx = planted_ctx()
    got = {n: metric(n).read(ctx) for n in READERS}
    assert got["grid_build_ms"] == pytest.approx(1e3 * (0.2 + 0.1) / 2)
    # the gather is the launch's own time
    assert got["sweep_launch_ms"] == pytest.approx(1e3 * (0.3 + 0.2) / 2)
    assert got["block_fold_ms"] == pytest.approx(1e3 * 0.1 / 2)
    # the second fetch lies past the window; the first is cut at 3.0
    assert got["result_wait_ms"] == pytest.approx(1e3 * (0.35 + 0.7) / 2)
    # self time: a parent loses what its children cover
    assert metric("_program_spans").self_ms_per(
        ctx, "repro.scenario.run", "grid_runs") == pytest.approx(
            1e3 * 0.05 / 2)
    assert metric("_program_spans").self_ms_per(
        ctx, "repro.scenario.launch", "grid_runs") == pytest.approx(
            1e3 * 0.4 / 2)


def test_self_time_divides_by_the_named_count(monkeypatch):
    monkeypatch.setattr(spans, "records", planted)
    p = metric("_program_spans")
    ctx = planted_ctx(windows=5)
    assert p.self_ms_per(ctx, "repro.scenario.fold", "windows") == \
        pytest.approx(1e3 * 0.1 / 5)


def test_plane_readers_on_planted_spans(monkeypatch):
    monkeypatch.setattr(spans, "records", planted_plane)
    ctx = planted_ctx(grid_runs=None, windows=2)
    got = {n: metric(n).read(ctx) for n in PLANE_READERS}
    # window B's observe is cut at 3.0
    assert got["plane_observe_ms"] == pytest.approx(1e3 * (0.2 + 0.05) / 2)
    assert got["plane_pool_ms"] == pytest.approx(1e3 * (0.05 + 0.05
                                                       + 0.05) / 2)
    assert got["plane_route_ms"] == pytest.approx(1e3 * 0.15 / 2)
    # the sweep's readers find no span of theirs, the plane's no count
    assert metric("grid_build_ms").read(ctx) is None
    for n in PLANE_READERS:
        assert metric(n).read(planted_ctx(windows=0)) is None


def test_readers_silent_without_program_spans(monkeypatch):
    monkeypatch.setattr(spans, "records", list)
    for n in READERS:
        assert metric(n).read(planted_ctx()) is None
    monkeypatch.setattr(spans, "records", planted)
    for n in READERS:
        assert metric(n).read(planted_ctx(grid_runs=0)) is None
    ctx = planted_ctx()
    ctx["recorder"].spans.clear()
    assert metric("grid_build_ms").read(ctx) is None


def test_traced_served_cell_reports_the_plane_layers(tmp_path):
    root = make_checkout(tmp_path)
    spans.clear()
    rc, res, err = run_cell(root, "tiny_static", seconds=1.0, trace=1)
    assert rc == 0 and res["correct"], err
    got = {n: res["metrics"][n]["value"] for n in PLANE_READERS}
    assert all(v > 0 for v in got.values()), got
    wins = [r for r in spans.records() if isinstance(r, spans.Span)
            and r.name == "repro.plane.window"]
    assert wins
    mean_ms = 1e3 * sum(r.t1 - r.t0 for r in wins) / len(wins)
    # a layer's self time a window fits in the admission window
    assert sum(got.values()) <= mean_ms
    assert got["plane_observe_ms"] * len(wins) <= \
        1e3 * res["device"]["window_s"]


def test_traced_blocked_sweep_reports_the_phases(tmp_path):
    root = make_checkout(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for n in READERS:
        spec["per_layer"].append(
            {"name": n, "unit": "ms/grid", "better": "lower",
             "source": "program_span", "layer": "sweep engine",
             "moves": "sweep_req_per_s", "workloads": ["tiny_blocked"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    spans.clear()
    rc, res, err = run_cell(root, "tiny_blocked", seconds=1.0, trace=1)
    assert rc == 0 and res["correct"], err
    for n in READERS:
        assert res["metrics"][n]["value"] > 0, n
    recs = [r for r in spans.records() if isinstance(r, spans.Span)]
    runs = {r.span_id: r for r in recs if r.name == "repro.scenario.run"}
    assert runs
    covered = sum(r.t1 - r.t0 for r in recs
                  if r.parent_id in runs and r.name in PHASES)
    total = sum(r.t1 - r.t0 for r in runs.values())
    assert covered >= 0.95 * total
