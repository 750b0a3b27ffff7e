"""The fault plane's pinning suite (``repro.core.faults``).

Four layers of protection:

  * golden regression — every ``faults=None`` scenario stays bit-identical
    to ``tests/golden_faults_pr9.json`` (the pre-fault engine's values;
    see ``scripts/capture_golden_engine.py``), on a single device AND a
    forced 4-device mesh, cloud-active scenarios included (the fault plane
    rewires the simulator's uplink branch);
  * routing properties — no policy ever selects a masked-down pair; the
    degraded fallback is the healthy argmin-latency pair and counts an
    SLO violation; every moscore backend agrees bit-identically under a
    mask; fault realizations are invariant to window partitioning and
    user blocks (fold_in-keyed draws, no carried state);
  * request-plane properties — :class:`AsyncExecutorPool` conserves
    requests under any interleaving of submissions, polls and
    ``fail_pairs`` kills; drift and fault throttles compose in the
    documented order ``truth = (prof x drift) x fault``, independent of
    call order;
  * integration — the gateway adopts a scenario's fault schedule and the
    serving plane retries failed work with bounded attempts.
"""

import json
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dispatch import DriftSchedule, OnlineDispatch
from repro.core.faults import FaultSchedule
from repro.core.policies import POLICY_CODES, mo_select_batch, select_pair
from repro.core.profiles import ProfileTable, paper_fleet
from repro.core.scenario import Scenario, Sweep, records, run
from repro.kernels.moscore import moscore_route
from repro.serving.executor import AsyncExecutorPool
from repro.serving.gateway import WindowedGateway

REPO = Path(__file__).resolve().parent.parent
GOLDEN = REPO / "tests" / "golden_faults_pr9.json"

PROF = paper_fleet()
P = PROF.n_pairs

f32 = jnp.float32


def _golden():
    return json.loads(GOLDEN.read_text())


# ------------------------------------------------- golden regression --

def test_records_bit_identical_to_pr9_golden():
    """Every record scenario captured pre-FaultSchedule replays
    bit-for-bit through the fault-aware engine with faults=None —
    including the cloud-active scenarios, whose uplink/RTT branch the
    WAN-jitter hook rewired — and its spec is still canonical."""
    for entry in _golden()["records"]:
        sc = Scenario.from_json(entry["scenario"])
        assert sc.to_json() == entry["scenario"]
        recs = records(sc)
        for k, want in entry["records"].items():
            np.testing.assert_array_equal(
                np.asarray(recs[k], np.float64), np.asarray(want),
                err_msg=f"{entry['scenario']}:{k}")


@pytest.mark.parametrize("fixture", ["sweep", "cloud_sweep"])
def test_sweeps_bit_identical_to_pr9_golden(fixture):
    fix = _golden()[fixture]
    base = Scenario.from_json(fix["scenario"])
    assert base.to_json() == fix["scenario"]
    res = run(base, Sweep(policy=tuple(fix["policies"]),
                          n_users=tuple(fix["user_levels"]),
                          seed=tuple(fix["seeds"])))
    for k, want in fix["metrics"].items():
        np.testing.assert_array_equal(np.asarray(res[k], np.float64),
                                      np.asarray(want), err_msg=k)


_SUBPROC_CHECK = """
import json
import jax, numpy as np
from repro.core.faults import FaultSchedule
from repro.core.scenario import Scenario, Sweep, run
from repro.launch.mesh import make_sweep_mesh

assert len(jax.devices()) == 4, jax.devices()
mesh = make_sweep_mesh()

# faults=None sharded across 4 real devices still reproduces the PR 9
# golden sweep; only the percentile metric gets the usual 1-float32-ULP
# allowance (XLA FMA contraction varies with the compiled batch shape).
fix = json.load(open({golden!r}))["sweep"]
res = run(Scenario.from_json(fix["scenario"]),
          Sweep(policy=tuple(fix["policies"]),
                n_users=tuple(fix["user_levels"]),
                seed=tuple(fix["seeds"])), mesh=mesh)
for k, want in fix["metrics"].items():
    if k == "latency_p90_ms":
        np.testing.assert_allclose(np.asarray(res[k], np.float64),
                                   np.asarray(want), rtol=3e-7, err_msg=k)
    else:
        np.testing.assert_array_equal(np.asarray(res[k], np.float64),
                                      np.asarray(want), err_msg=k)

# fault-ACTIVE sweeps shard bitwise too: the FaultMeta replicates to every
# device and the epoch draws key on absolute step indices, so sharded ==
# single for every metric including the availability ones.
fsc = Scenario(n_requests=150,
               faults=FaultSchedule(down_rate=0.08, epoch=25,
                                    outages=((1, 30, 80),)))
fsw = Sweep(policy=("MO", "LT"), n_users=(3, 7), seed=(0,))
ref = run(fsc, fsw)
out = run(fsc, fsw, mesh=mesh)
for k in ref.metric_names:
    if k in ("latency_p90_ms", "latency_p99_ms"):   # percentiles: 1 ULP
        np.testing.assert_allclose(out[k], ref[k], rtol=3e-7, err_msg=k)
    else:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)
assert "slo_violation_share" in ref.metric_names
print("OK")
"""


def test_faults_golden_in_forced_4_device_subprocess():
    """PR 9 golden + fault-active sharding on a real 4-device mesh
    (xla_force_host_platform_device_count in a fresh process)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    src = _SUBPROC_CHECK.format(golden=str(GOLDEN))
    res = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


# ---------------------------------------------------- spec / hashing --

def test_fault_schedule_json_roundtrip():
    # a default schedule is inert and serializes to the minimal spec
    assert FaultSchedule().to_json() == {}
    assert not FaultSchedule().active
    fs = FaultSchedule(down_rate=0.05, epoch=25, throttle_rate=0.1,
                       rtt_jitter_ms=30.0, bw_jitter=0.5, timeout_ms=500.0,
                       max_attempts=2, visible=False,
                       outages=((2, 40, 90), (0, 10, 20)), seed=7)
    back = FaultSchedule.from_json(json.loads(json.dumps(fs.to_json())))
    assert back == fs and hash(back) == hash(fs)
    assert back.to_json() == fs.to_json()
    assert FaultSchedule.from_json(None) is None
    # only-when-set: defaulted knobs never appear in the spec
    assert set(FaultSchedule(down_rate=0.1).to_json()) == {"down_rate"}


def test_fault_schedule_validation():
    for bad in (dict(down_rate=1.0), dict(down_rate=-0.1),
                dict(throttle_rate=1.5), dict(epoch=0),
                dict(throttle_t_mult=0.0), dict(rtt_jitter_ms=-1.0),
                dict(bw_jitter=-0.5), dict(timeout_ms=-1.0),
                dict(max_attempts=0), dict(outages=((0, 50, 50),)),
                dict(outages=((-1, 0, 10),)), dict(outages=((0, 10),))):
        with pytest.raises(ValueError):
            FaultSchedule(**bad)
    # scripted pair must exist in the (extended) fleet
    with pytest.raises(ValueError, match="pair 9"):
        FaultSchedule(outages=((9, 0, 10),)).resolve(P)


def test_scenario_faults_spec_and_hash():
    """No-fault specs are untouched by the feature: no "faults" key,
    same hash as before; a fault scenario round-trips by value with a
    discriminating hash."""
    assert "faults" not in Scenario().to_json()
    assert Scenario(faults=None).hash == Scenario().hash
    fs = FaultSchedule(down_rate=0.05)
    sc = Scenario(n_users=5, faults=fs)
    back = Scenario.from_json(json.dumps(sc.to_json()))
    assert back == sc and back.hash == sc.hash
    assert back.faults == fs
    assert sc.hash != Scenario(n_users=5).hash
    assert Scenario(faults=FaultSchedule(down_rate=0.1)).hash \
        != Scenario(faults=FaultSchedule(down_rate=0.2)).hash


# ------------------------------------------------ routing properties --

@st.composite
def masked_case(draw):
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    nP = draw(st.integers(2, 12))
    nG = draw(st.integers(2, 6))
    prof = ProfileTable(jnp.asarray(rng.uniform(10, 500, (nP, nG))),
                        jnp.asarray(rng.uniform(0.01, 0.5, (nP, nG))),
                        jnp.asarray(rng.uniform(1, 99, (nP, nG))))
    health = rng.random(nP) > draw(st.floats(0.1, 0.9))
    if not health.any():
        health[int(rng.integers(0, nP))] = True
    gs = rng.integers(0, nG, 32)
    gamma = draw(st.floats(0.0, 1.0))
    delta = draw(st.floats(0.0, 60.0))
    return prof, jnp.asarray(health), jnp.asarray(gs, jnp.int32), \
        gamma, delta, rng


@settings(max_examples=30, deadline=None)
@given(masked_case())
def test_mo_routing_never_selects_masked_pair(case):
    """Property (satellite): under any health mask with at least one
    healthy pair, Algorithm 1 routes every request to a healthy pair —
    feasible-and-healthy when possible, the degraded argmin-latency
    fallback otherwise, never a down pair."""
    prof, health, gs, gamma, delta, _rng = case
    q0 = jnp.zeros((prof.n_pairs,), f32)
    ps, _ = mo_select_batch(prof, gs, q0, delta=delta, gamma=gamma,
                            health=health)
    h = np.asarray(health)
    assert h[np.asarray(ps)].all()


@settings(max_examples=15, deadline=None)
@given(masked_case())
def test_no_policy_selects_masked_pair(case):
    """The post-switch mask in policy_scores covers every baseline too:
    LC/LT/HA/RR/RND route around an outage exactly like MO."""
    prof, health, gs, gamma, delta, rng = case
    q0 = jnp.zeros((prof.n_pairs,), f32)
    key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
    h = np.asarray(health)
    for name, code in POLICY_CODES.items():
        p, _ = select_pair(jnp.asarray(code, jnp.int32), prof,
                           gs[0], q0, key, jnp.asarray(0, jnp.int32),
                           jnp.asarray(gamma, f32), jnp.asarray(delta, f32),
                           None, health)
        assert h[int(p)], name


def test_all_backends_agree_under_mask():
    """Every fp32 moscore backend produces the SAME bits under a health
    mask (the plain "pallas" kernel routes via the hoisted precompute);
    int8 stays under its bounded-mismatch contract."""
    rng = np.random.default_rng(3)
    gs = rng.integers(0, PROF.n_groups, 96)
    q0 = np.zeros(P, np.float32)
    for trial in range(4):
        health = jnp.asarray(rng.random(P) > 0.5).at[0].set(True)
        outs = {b: moscore_route(PROF.T, PROF.E, PROF.mAP, gs, q0,
                                 delta=15.0, gamma=0.4, backend=b,
                                 health=health)
                for b in ("pallas", "xla", "hoisted", "pallas_hoisted")}
        for b in ("pallas", "hoisted", "pallas_hoisted"):
            np.testing.assert_array_equal(np.asarray(outs[b][0]),
                                          np.asarray(outs["xla"][0]),
                                          err_msg=f"{trial}:{b}")
            np.testing.assert_array_equal(np.asarray(outs[b][1]),
                                          np.asarray(outs["xla"][1]),
                                          err_msg=f"{trial}:{b}")
        assert np.asarray(health)[np.asarray(outs["xla"][0])].all()
        ps8, _ = moscore_route(PROF.T, PROF.E, PROF.mAP, gs, q0,
                               delta=15.0, gamma=0.4, backend="int8",
                               health=health)
        assert np.asarray(health)[np.asarray(ps8)].all()


def test_degraded_fallback_is_healthy_argmin_latency():
    """When no healthy pair clears the accuracy bar, the defined
    degradation rule routes to the healthy pair with the lowest expected
    latency (gamma > 0): the accuracy term drops out of J."""
    g = 2
    best = int(np.argmax(np.asarray(PROF.mAP[:, g])))
    health = jnp.ones((P,), bool).at[best].set(False)
    # delta=0: only the argmax-mAP pair is feasible, and it is down
    gs = jnp.asarray([g], jnp.int32)
    q0 = jnp.zeros((P,), f32)
    ps, _ = mo_select_batch(PROF, gs, q0, delta=0.0, gamma=0.7,
                            health=health)
    h = np.asarray(health)
    lat = np.asarray(PROF.T[:, g], np.float64)
    lat[~h] = np.inf
    assert int(ps[0]) == int(np.argmin(lat))


def test_all_down_mask_relaxes_to_healthy():
    """A whole-fleet outage relaxes the router's mask to all-true (there
    is nobody else) while down_at still reports the outage for the truth
    model's stall and failed accounting."""
    meta = FaultSchedule(outages=tuple((p, 0, 10) for p in range(P))) \
        .resolve(P)
    assert np.asarray(meta.down_at(5)).all()
    assert np.asarray(meta.health_at(5)).all()
    assert not np.asarray(meta.down_at(10)).any()


def test_records_count_slo_violations_and_failures():
    """records() under a scripted outage reports the availability
    stream: failed marks requests dispatched into the outage (blind
    router), slo_violation marks steps where no healthy pair could clear
    the accuracy bar."""
    # pair 3 is the busiest pair of this scenario — the outage that hurts
    fs = FaultSchedule(outages=((3, 10, 60),), visible=False,
                       timeout_ms=2000.0)
    recs = records(Scenario(n_users=6, n_requests=120, seed=0, faults=fs))
    assert "failed" in recs and "slo_violation" in recs
    failed = np.asarray(recs["failed"])
    srv = np.asarray(recs["server"], np.int64)
    assert failed.sum() > 0                       # blind router pays
    assert (srv[failed > 0] == 3).all()           # only the down pair
    # the aware router avoids the down pair entirely during the window
    aware = records(Scenario(n_users=6, n_requests=120, seed=0,
                             faults=replace(fs, visible=True)))
    assert np.asarray(aware["failed"]).sum() == 0
    # failover-aware routing beats the blind router's stall-laden mean
    assert np.asarray(aware["latency"]).mean() \
        < np.asarray(recs["latency"]).mean()


# --------------------------------------------- realization invariance --

def _route_stream(sc, window, n=126):
    gw = WindowedGateway(sc, backend="hoisted")
    q = np.zeros(gw.prof.n_pairs, np.float32)
    ids = np.arange(n) % 9
    out = []
    for i in range(0, n, window):
        p, _g, q = gw.route_window(ids[i:i + window], q)
        out.append(np.asarray(p))
    return np.concatenate(out)


@pytest.mark.parametrize("policy", ["MO", "LT"])
def test_fault_draws_invariant_to_window_partition(policy):
    """The mask enters the gateway as health_at(absolute request index),
    so no partition of the stream into admission windows can change a
    decision — the same invariance contract as the RND key stream."""
    fs = FaultSchedule(down_rate=0.2, epoch=20, outages=((1, 30, 70),),
                       seed=5)
    sc = Scenario(n_users=9, n_requests=0, seed=2, policy=policy,
                  faults=fs)
    ref = _route_stream(sc, 126)
    for window in (1, 7, 64):
        np.testing.assert_array_equal(ref, _route_stream(sc, window),
                                      err_msg=f"W={window}")
    # the schedule actually bit: some decisions differ from fault-free
    assert (ref != _route_stream(replace(sc, faults=None), 126)).any()


def test_fault_realization_invariant_to_user_block():
    """Fault draws key on the per-user step index, never on the block
    shape or batch position: a single-block run is bit-identical to the
    un-blocked engine, and every block row of a multi-block fault grid
    equals its own solo run (the useraxis contract, extended to the
    availability metrics)."""
    from repro.core.dispatch import StaticDispatch
    from repro.core.simulator import (ConfigGrid, SimConfig,
                                      _make_user_grid, _sweep_summaries)
    from repro.core.workload import MarkovWorkload

    fs = FaultSchedule(down_rate=0.15, epoch=10, throttle_rate=0.2,
                       seed=3)
    base = Scenario(n_users=20, n_requests=80, seed=1, faults=fs)
    ref, one_block = run(base), run(replace(base, user_block=20))
    assert "slo_violation_share" in ref.metric_names
    for k in ref.metric_names:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(one_block[k]), err_msg=k)

    meta = fs.resolve(P)
    grid, _seg = _make_user_grid(
        PROF, [SimConfig(n_users=20, n_requests=80, seed=1)], 8)
    wl, de = MarkovWorkload(), StaticDispatch()
    per_block = _sweep_summaries(PROF, wl, de, None, None, meta, grid,
                                 n_requests=80, warmup=12, mesh=None)
    assert "failed_share" in per_block
    for b in range(grid.n_configs):
        row = ConfigGrid(*[leaf[b:b + 1] for leaf in grid])
        solo = _sweep_summaries(PROF, wl, de, None, None, meta, row,
                                n_requests=80, warmup=12, mesh=None)
        for k in per_block:
            np.testing.assert_array_equal(
                np.asarray(per_block[k][b]), np.asarray(solo[k][0]),
                err_msg=f"block {b}: {k}")


def test_faults_sweep_axis_and_mixed_fill():
    """faults is a sweepable Scenario axis; a sweep mixing faults=None
    with live schedules still reports rectangular availability metrics
    (zeros on the no-fault slices)."""
    res = run(Scenario(n_users=5, n_requests=100, seed=0),
              Sweep(faults=[None, FaultSchedule(down_rate=0.3, epoch=10)]))
    slo = np.asarray(res["slo_violation_share"], np.float64).ravel()
    failed = np.asarray(res["failed_share"], np.float64).ravel()
    assert slo.shape == (2,) and failed.shape == (2,)
    assert slo[0] == 0.0 and failed[0] == 0.0
    p99 = np.asarray(res["latency_p99_ms"], np.float64).ravel()
    assert p99[0] == 0.0 and p99[1] > 0.0      # zeros-fill on the None slice


# ------------------------------------------------ request-plane props --

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 40), st.integers(0, 2**31 - 1))
def test_pool_conserves_requests_under_faults(n_ops, seed):
    """Property (satellite): under any interleaving of window
    submissions, out-of-order polls and fail_pairs kills (random down
    masks and timeouts), the pool conserves requests —
    submitted == polled + failed + in_flight — and depths stay
    non-negative; every rid surfaces exactly once (polled XOR failed)."""
    rng = np.random.default_rng(seed)
    pool = AsyncExecutorPool(PROF)
    now, rid = 0.0, 0
    seen_polled, seen_failed = [], []
    for _ in range(n_ops):
        r = rng.random()
        if r < 0.5:
            w = int(rng.integers(1, 9))
            pool.submit_window(rng.integers(0, P, w),
                               rng.integers(0, PROF.n_groups, w), now,
                               rids=np.arange(rid, rid + w))
            rid += w
        elif r < 0.8:
            now += float(rng.uniform(0.0, 2.0))
            done = pool.poll(now)
            assert (done.finish_s <= now).all()
            seen_polled.extend(done.rids.tolist())
        else:
            down = rng.random(P) < 0.4
            t_s = float(rng.uniform(0.5, 3.0)) if rng.random() < 0.5 \
                else None
            failed = pool.fail_pairs(down, now, timeout_s=t_s)
            assert (failed.finish_s > now).all()     # never completions
            if t_s is None:
                assert down[failed.pairs].all()
            seen_failed.extend(failed.rids.tolist())
        assert (pool._depth >= 0).all()
        assert pool.submitted == pool.polled + pool.failed + pool.in_flight
    tail = pool.poll(np.inf)
    seen_polled.extend(tail.rids.tolist())
    assert pool.in_flight == 0 and (pool._depth == 0).all()
    assert sorted(seen_polled + seen_failed) == list(range(rid))


def test_fail_pairs_rebuilds_fifo_frontier():
    """Killing a down pair's backlog frees its FIFO frontier: work
    submitted after recovery is not serialized behind ghost requests."""
    pool = AsyncExecutorPool(PROF)
    svc = float(pool._T_s[0].max())
    pool.submit_window(np.zeros(50, np.int64),
                       np.full(50, PROF.n_groups - 1), 0.0,
                       rids=np.arange(50))
    backlog = float(pool._avail[0])
    down = np.zeros(P, bool)
    down[0] = True
    failed = pool.fail_pairs(down, 0.1)
    assert failed.size == 50 and pool.failed == 50
    assert pool._avail[0] <= 0.1
    # recovered pair: a fresh request finishes in ~one service time
    resp = pool.submit_window(np.zeros(1, np.int64), np.zeros(1, np.int64),
                              0.2, rids=np.asarray([50]))
    assert float(resp.finish_s[0]) <= 0.2 + svc < backlog


def test_drift_and_fault_throttle_compose_order_independent():
    """truth = (prof x drift) x fault, bitwise, whatever order the two
    hooks fire in: drift is a cumulative multiplier, the fault throttle
    SETs its factor (a pure function of the fault step)."""
    drift_t = np.linspace(1.1, 2.0, P * PROF.n_groups).reshape(
        P, PROF.n_groups)
    fault_t = np.where(np.arange(P) % 2 == 0, 3.0, 1.0)[:, None]
    a = AsyncExecutorPool(PROF)
    a.apply_drift(drift_t, 1.5)
    a.set_fault_throttle(fault_t, np.full((P, 1), 1.25))
    b = AsyncExecutorPool(PROF)
    b.set_fault_throttle(fault_t, np.full((P, 1), 1.25))
    b.apply_drift(drift_t, 1.5)
    np.testing.assert_array_equal(a._T_s, b._T_s)
    np.testing.assert_array_equal(a._E, b._E)
    want = (np.asarray(PROF.T, np.float64) / 1000.0 * drift_t) * fault_t
    np.testing.assert_array_equal(a._T_s, want)
    # SET semantics: clearing the throttle restores pure drift
    a.set_fault_throttle(1.0)
    np.testing.assert_array_equal(
        a._T_s, np.asarray(PROF.T, np.float64) / 1000.0 * drift_t)


def test_simulator_composes_drift_and_fault_throttle():
    """The simulator's truth model applies the same order: with a
    fleet-wide deterministic drift and an (epoch-keyed) fault throttle
    active together, observed latencies scale multiplicatively on the
    slowed steps — never less than the drift-only run."""
    drift = DriftSchedule.throttle(PROF, 2, at_step=20, t_mult=1.5)
    base = Scenario(n_users=5, n_requests=100, seed=0, drift=drift)
    both = replace(base, faults=FaultSchedule(throttle_rate=0.6,
                                              epoch=10, seed=2,
                                              throttle_t_mult=4.0))
    lat_d = np.asarray(records(base)["latency"])
    lat_b = np.asarray(records(both)["latency"])
    assert lat_b.mean() > lat_d.mean()


# ------------------------------------------------ serving integration --

def test_gateway_adopts_scenario_faults_and_masks():
    fs = FaultSchedule(outages=((3, 0, 10_000),))
    gw = WindowedGateway(Scenario(n_users=8, faults=fs),
                         backend="hoisted")
    assert gw._fault_meta is not None and gw._fault_meta.visible
    pairs, _, _ = gw.route_window(np.arange(64) % 8, np.zeros(P))
    assert not (np.asarray(pairs) == 3).any()
    # blind schedule: the router keeps the fused no-mask path
    blind = WindowedGateway(
        Scenario(n_users=8, faults=replace(fs, visible=False)))
    assert blind._fault_meta is not None and not blind._fault_meta.visible
    # an inert schedule costs nothing at all
    assert WindowedGateway(paper_fleet(),
                           faults=FaultSchedule())._fault_meta is None


def test_pods_with_faults_raises():
    with pytest.raises(ValueError, match="fault mask"):
        WindowedGateway(paper_fleet(),
                        faults=FaultSchedule(down_rate=0.1),
                        pods=[0, 0, 1, 1, 2])


def test_serving_plane_retries_with_bounded_attempts():
    """End-to-end failover loop: an outage on the busiest pair fails its
    in-flight work, the plane re-routes the victims (at most
    max_attempts tries), the pool conserves every request, and the
    availability metrics surface in summarize()."""
    from repro.serving.engine import ServingPlane

    fs = FaultSchedule(outages=((3, 40, 160),), timeout_ms=400.0,
                       max_attempts=2)
    sc = Scenario(n_users=12, n_requests=0, seed=3, policy="MO", faults=fs)
    plane = ServingPlane.build(sc, window=16, offered_rps=30.0)
    recs = plane.run(240)
    pool = plane.pool
    assert pool.submitted == pool.polled + pool.failed + pool.in_flight
    assert pool.in_flight == 0
    assert plane.retried > 0
    # every offered request either completed or was dropped for good
    assert recs["latency"].size == 240 - plane.failed_requests
    s = ServingPlane.summarize(recs)
    assert {"failed_share", "retried_share", "latency_p99_ms"} <= set(s)
    assert 0.0 <= s["failed_share"] <= 1.0
    assert s["latency_p99_ms"] >= s["latency_p90_ms"]
    # a fault-free plane reports no availability keys (old contract)
    clean = ServingPlane.build(replace(sc, faults=None), window=16,
                               offered_rps=30.0)
    s0 = ServingPlane.summarize(clean.run(96))
    assert "failed_share" not in s0
