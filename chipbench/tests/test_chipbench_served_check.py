"""The served cells' comparison with the plain reference, on tiny cells on
the CPU: a sound run is correct, the rates are all the work over all
the time, the bfloat16 control fails (and, under faults, the control
blind to the health mask), and each fault the served path can have,
planted underneath a whole run, makes ``correct`` false through the
check named for it."""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest
from chipbench_testkit import make_checkout, run_cell

from chipbench import bench
from chipbench.spans import Recorder

FAULT_SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("served"))


@pytest.mark.parametrize("workload", ["tiny_static", "tiny_online",
                                      "tiny_faults"])
def test_sound_run_is_correct(root, workload):
    rc, res, err = run_cell(root, workload, seed=2 ** 31 + 11)
    assert rc == 0 and res["correct"], err
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"plane_req_per_s", "setup_s"}
    assert res["checks"]["group_mismatch"]["value"] == 0
    assert err.strip().splitlines()[-1].startswith("check ")
    faulted = {"retry_mismatch", "service_mismatch"} & set(res["checks"])
    assert bool(faulted) == (workload == "tiny_faults")
    assert len(faulted) in (0, 2)


def test_faulted_run_kills_retries_and_drops(root):
    cell = bench.load_cell(root, "tiny_faults")
    drv = bench.driver(cell).setup(cell.config, cell.traffic, FAULT_SEED,
                                   Recorder())
    drv.window(1.0)
    kills = [ev for ev in drv.rec.log if ev[0] == "pool.fail_pairs"]
    assert sum(ev[3].size for ev in kills) > 0
    # one fault phase a routed window: the kill step and the throttle row
    routes = [ev for ev in drv.rec.log if ev[0] == "gateway.route_window"]
    throttles = [ev for ev in drv.rec.log
                 if ev[0] == "pool.set_fault_throttle"]
    assert len(kills) == len(throttles) == len(routes)
    assert any(np.any(np.asarray(ev[1][0]) != 1.0) for ev in throttles)
    assert drv.plane.retried > 0
    attempted, failed = drv.attempted()
    # failed counts the requests dropped in the window, not killed tries
    assert 0 < failed < drv.plane.pool.failed
    checks = drv.check()
    assert checks["retry_mismatch"] == checks["service_mismatch"] == 0


@pytest.mark.parametrize("key,value", [
    ("seed", 3), ("visible", False), ("rtt_jitter_ms", 5.0),
    ("bw_jitter", 0.2), ("down_rates", 0.1)])
def test_unchecked_fault_schedule_is_refused(root, key, value):
    cell = bench.load_cell(root, "tiny_faults")
    config = dict(cell.config,
                  faults=dict(cell.config["faults"], **{key: value}))
    with pytest.raises(ValueError, match=key):
        bench.driver(cell).setup(config, cell.traffic, 3, Recorder())


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
    assert drv.controls() == ("bfloat16",)
    assert drv.check(control="bfloat16")["decision_gap"] > limit


def test_blind_control_fails(root):
    cell = bench.load_cell(root, "tiny_faults")
    drv = bench.driver(cell).setup(cell.config, cell.traffic, 5, Recorder())
    drv.window(0.3)
    assert drv.controls() == ("bfloat16", "blind")
    limit = cell.traffic["limits"]["decision_gap"]
    assert drv.check()["decision_gap"] <= limit
    assert drv.check(control="blind")["decision_gap"] > limit


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


def _unmasked(orig):
    def route(self, stream_ids, queue_depths):
        """The router blind to the health mask: the fused kernel."""
        meta, self._fault_meta = self._fault_meta, None
        try:
            return orig(self, stream_ids, queue_depths)
        finally:
            self._fault_meta = meta
    return route


def _drop_one(orig):
    def requeue(self, failed):
        """A victim left out of the retry queue, uncounted."""
        keep = {f.name: getattr(failed, f.name)[1:]
                for f in dataclasses.fields(failed)}
        return orig(self, type(failed)(**keep))
    return requeue


def _past_cap(orig):
    def requeue(self, failed):
        """Victims retried one try past ``max_attempts``."""
        faults = self.gateway.faults
        self.gateway.faults = dataclasses.replace(
            faults, max_attempts=faults.max_attempts + 1)
        try:
            return orig(self, failed)
        finally:
            self.gateway.faults = faults
    return requeue


def _kill_healthy(orig):
    def fail_pairs(self, down, now, *, timeout_s=None):
        """One more pair, up and busy, killed with the down ones."""
        down = np.asarray(down, bool).copy()
        busy = {int(p) for w in self._pending for p, f in
                zip(w.pairs, w.finish_s) if f > now}
        healthy = sorted(busy - set(np.flatnonzero(down).tolist()))
        if healthy:
            down[healthy[0]] = True
        return orig(self, down, now, timeout_s=timeout_s)
    return fail_pairs


def _uncounted(orig):
    def fail_pairs(self, down, now, *, timeout_s=None):
        """Kills that leave ``failed`` as it was."""
        failed = self.failed
        out = orig(self, down, now, timeout_s=timeout_s)
        self.failed = failed
        return out
    return fail_pairs


def _kill_none(orig):
    def fail_pairs(self, down, now, *, timeout_s=None):
        """A kill step that kills nothing."""
        return orig(self, np.zeros_like(down), now)
    return fail_pairs


def _no_down(orig):
    """The plane's kill step skipped: the schedule reads as one that
    takes no pair down (the router still masks)."""
    return property(lambda self: False)


def _throttle_ignored(orig):
    def set_fault_throttle(self, t_mult, e_mult=None):
        """Multipliers taken and never applied."""
    return set_fault_throttle


def _throttle_misplaced(orig):
    def set_fault_throttle(self, t_mult, e_mult=None):
        """Each pair's multipliers applied to the next pair."""
        return orig(self, np.roll(t_mult, 1, axis=0),
                    None if e_mult is None else np.roll(e_mult, 1, axis=0))
    return set_fault_throttle


@pytest.mark.parametrize("fault,check", [
    ("mask_ignored", "decision_gap"), ("victim_dropped", "retry_mismatch"),
    ("retried_past_cap", "retry_mismatch"),
    ("healthy_killed", "retry_mismatch"),
    ("kill_uncounted", "pool_unbalanced"),
    ("kills_nothing", "retry_mismatch"),
    ("fault_phase_skipped", "retry_mismatch"),
    ("throttle_ignored", "service_mismatch"),
    ("throttle_misplaced", "service_mismatch")])
def test_failover_fault_makes_correct_false(root, monkeypatch, fault, check):
    from repro.core.faults import FaultMeta
    from repro.serving import ServingPlane
    from repro.serving.executor import AsyncExecutorPool
    from repro.serving.gateway import WindowedGateway

    target, name, plant = {
        "mask_ignored": (WindowedGateway, "route_window", _unmasked),
        "victim_dropped": (ServingPlane, "_requeue", _drop_one),
        "retried_past_cap": (ServingPlane, "_requeue", _past_cap),
        "healthy_killed": (AsyncExecutorPool, "fail_pairs", _kill_healthy),
        "kill_uncounted": (AsyncExecutorPool, "fail_pairs", _uncounted),
        "kills_nothing": (AsyncExecutorPool, "fail_pairs", _kill_none),
        "fault_phase_skipped": (FaultMeta, "has_down", _no_down),
        "throttle_ignored": (AsyncExecutorPool, "set_fault_throttle",
                             _throttle_ignored),
        "throttle_misplaced": (AsyncExecutorPool, "set_fault_throttle",
                               _throttle_misplaced),
    }[fault]
    monkeypatch.setattr(target, name, plant(getattr(target, name)))
    rc, res, err = run_cell(root, "tiny_faults", seed=FAULT_SEED)
    assert rc == 0 and res["correct"] is False, err
    failing = {k for k, v in res["checks"].items() if v["value"] > v["limit"]}
    assert check in failing, json.dumps(res["checks"])
