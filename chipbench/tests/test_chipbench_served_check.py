"""The served cells' comparison with the plain reference, on tiny cells on
the CPU: a sound run is correct, the rates are all the work over all
the time, the bfloat16 control fails, and each fault the served path
can have, planted underneath a whole run, makes ``correct`` false."""

from __future__ import annotations

import numpy as np
import pytest
from chipbench_testkit import make_checkout, run_cell

from chipbench import bench
from chipbench.spans import Recorder


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("served"))


@pytest.mark.parametrize("workload", ["tiny_static", "tiny_online"])
def test_sound_run_is_correct(root, workload):
    rc, res, err = run_cell(root, workload, seed=2 ** 31 + 11)
    assert rc == 0 and res["correct"], err
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"plane_req_per_s", "setup_s"}
    assert res["checks"]["group_mismatch"]["value"] == 0
    assert err.strip().splitlines()[-1].startswith("check ")


def test_rates_are_all_work_over_all_time(root):
    cell = bench.load_cell(root, "tiny_static")
    drv = bench.driver(cell).setup(cell.config, cell.traffic, 3, Recorder())
    out = drv.window(0.5)
    assert out["plane_req_per_s"] == pytest.approx(drv.requests / drv.wall)
    assert drv.requests % (cell.traffic["window"]
                           * cell.traffic["windows_per_call"]) == 0
    # every admission window of the window is counted, and each is full
    routes = [ev for ev in drv.rec.log[drv.log_start:]
              if ev[0] == "gateway.route_window"]
    assert len(routes) == drv.windows
    assert sum(len(ev[1][0]) for ev in routes) == drv.requests


@pytest.mark.parametrize("workload", ["tiny_static", "tiny_online"])
def test_control_fails(root, workload):
    cell = bench.load_cell(root, workload)
    drv = bench.driver(cell).setup(cell.config, cell.traffic, 5, Recorder())
    drv.window(0.3)
    limit = cell.traffic["limits"]["decision_gap"]
    assert drv.check()["decision_gap"] <= limit
    assert drv.check(control=True)["decision_gap"] > limit


def _frozen(self, stream_ids, detected_counts):
    """A step that returns its state unchanged."""


def _half(orig):
    def half(self, stream_ids, detected_counts):
        n = (len(stream_ids) + 1) // 2     # a batch of one stays whole
        return orig(self, np.asarray(stream_ids)[:n],
                    np.asarray(detected_counts)[:n])
    return half


def _altered(orig):
    def altered(self, stream_ids, queue_depths):
        pairs, gs, q = orig(self, stream_ids, queue_depths)
        pairs = np.asarray(pairs).copy()
        pairs[len(pairs) // 2] = (pairs[len(pairs) // 2] + 1) \
            % self.prof.n_pairs
        return pairs, gs, q
    return altered


@pytest.mark.parametrize("workload,fault", [
    ("tiny_static", "state_unchanged"), ("tiny_static", "half_batch"),
    ("tiny_static", "answer_altered"), ("tiny_online", "belief_unchanged")])
def test_fault_makes_correct_false(root, monkeypatch, workload, fault):
    from repro.serving.gateway import WindowedGateway

    obs = WindowedGateway.observe_detections_window
    if fault == "state_unchanged":
        monkeypatch.setattr(WindowedGateway, "observe_detections_window",
                            _frozen)
    elif fault == "belief_unchanged":
        monkeypatch.setattr(WindowedGateway, "observe_window",
                            lambda self, *a, **k: None)
    elif fault == "half_batch":
        monkeypatch.setattr(WindowedGateway, "observe_detections_window",
                            _half(obs))
    else:
        monkeypatch.setattr(WindowedGateway, "route_window",
                            _altered(WindowedGateway.route_window))
    rc, res, _ = run_cell(root, workload, seed=9)
    assert rc == 0 and res["correct"] is False
