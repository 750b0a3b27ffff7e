"""The sweep cells' comparison with the plain reference, on tiny cells on
the CPU: a sound run is correct (one fused grid, and a user-blocked
grid with its segment aggregation), the rate is all the work over all
the time, the bfloat16 control fails, and each fault the sweep path can
have, planted underneath a whole run, makes ``correct`` false."""

from __future__ import annotations

import jax
import pytest
from chipbench_testkit import make_checkout, run_cell

from chipbench import bench
from chipbench.spans import Recorder


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("sweep"))


@pytest.fixture
def fresh():
    """Planted faults sit inside jitted programs: retrace around them."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("workload", ["tiny_sweep", "tiny_blocked"])
def test_sound_run_is_correct(root, workload):
    rc, res, err = run_cell(root, workload, seed=2 ** 31 + 3)
    assert rc == 0 and res["correct"], err
    assert set(res["metrics"]) == {"sweep_req_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_rate_is_all_work_over_all_time(root):
    cell = bench.load_cell(root, "tiny_blocked")
    drv = bench.driver(cell).setup(cell.config, cell.traffic, 3, Recorder())
    t = cell.traffic
    assert drv.rows_per_grid == len(t["policies"]) * 5     # 23 users, 5/block
    out = drv.window(0.3)
    assert out["sweep_req_per_s"] * 0.3 <= drv.runs * drv.per_grid
    assert drv.per_grid == drv.rows_per_grid * t["n_requests"]


def test_control_fails(root):
    cell = bench.load_cell(root, "tiny_sweep")
    drv = bench.driver(cell).setup(cell.config, cell.traffic, 4, Recorder())
    drv.window(0.1)
    lim = cell.traffic["limits"]
    assert all(v <= lim[k] for k, v in drv.check().items())
    assert any(v > lim[k] for k, v in drv.check(control="bfloat16").items())


def _patch(monkeypatch, fault):
    from repro.core import scenario, simulator, workload

    if fault == "state_unchanged":
        monkeypatch.setattr(workload.MarkovWorkload, "next_count",
                            lambda self, ctx, key, cur, user, pos: cur)
    elif fault == "half_batch":
        orig = simulator._summarize_core

        def half(recs, prof, warmup, cloud=None, *, with_hist=False):
            n = recs["latency"].shape[0]
            keep = {k: v[:warmup + (n - warmup) // 2]
                    for k, v in recs.items()}
            return orig(keep, prof, warmup, cloud, with_hist=with_hist)

        monkeypatch.setattr(simulator, "_summarize_core", half)
    elif fault == "answer_altered":
        orig_run = scenario.run

        def altered(*a, **k):
            res = orig_run(*a, **k)
            res.metrics["latency_ms"].reshape(-1)[0] *= 1.1
            return res

        monkeypatch.setattr(scenario, "run", altered)
    else:   # exchange_left_out: rows past the first shard never arrive
        orig = simulator._sweep_summaries

        def unshared(*a, **k):
            out = orig(*a, **k)
            return {m: v.at[..., v.shape[-1] // 4:].set(0)
                    if m != "latency_hist" else v for m, v in out.items()}

        monkeypatch.setattr(simulator, "_sweep_summaries", unshared)


@pytest.mark.parametrize("workload,fault", [
    ("tiny_sweep", "state_unchanged"), ("tiny_sweep", "half_batch"),
    ("tiny_sweep", "answer_altered"), ("tiny_blocked", "exchange_left_out")])
def test_fault_makes_correct_false(root, monkeypatch, fresh, workload,
                                   fault):
    _patch(monkeypatch, fault)
    rc, res, _ = run_cell(root, workload, seed=6, seconds=0.3)
    assert rc == 0 and res["correct"] is False
