"""The Scenario/Sweep/Results contract (ISSUE 5): declarative scenarios
round-trip through JSON exactly, the scenario path reproduces the legacy
kwarg engine bit for bit (pinned by the PR 3 golden fixture — do NOT
regenerate it), any Scenario field sweeps as a named axis (config-leaf
axes as ONE fused program), and the legacy entry points are
deprecation-warned shims over this path."""

import json
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import scenario as SC
from repro.core.dispatch import (DriftSchedule, OnlineDispatch,
                                 StaticDispatch)
from repro.core.profiles import paper_fleet, stack_profiles, synthetic_fleet
from repro.core.scenario import (LegacyAPIWarning, Results, Scenario,
                                 Sweep, records, run)
from repro.core.simulator import SimConfig, summarize
from repro.data.traces import synthetic_trace

GOLDEN = Path(__file__).resolve().parent / "golden_static_pr3.json"

LEGACY_OK = pytest.mark.filterwarnings(
    "ignore::repro.core.scenario.LegacyAPIWarning")


def _golden():
    with open(GOLDEN) as f:
        return json.load(f)


# ------------------------------------------------- JSON round-tripping --

def _drift():
    return DriftSchedule.throttle(paper_fleet(), 4, at_step=60,
                                  t_mult=3.0, e_mult=8.0, recover_step=90)


@pytest.mark.parametrize("workload", ["none", "markov", "trace"])
@pytest.mark.parametrize("dispatch", ["none", "static", "online",
                                      "windowed"])
@pytest.mark.parametrize("drift", ["none", "throttle"])
@pytest.mark.parametrize("cloud", ["none", "tier"])
def test_scenario_roundtrip_all_component_combos(workload, dispatch,
                                                 drift, cloud):
    """Scenario.from_json(s.to_json()) == s over the full component cube
    (workload x dispatch x drift x cloud), via the dict AND the JSON
    string, with a stable hash."""
    from repro.core.cloud import CloudTier
    from repro.core.workload import MarkovWorkload

    wl = {"none": None, "markov": MarkovWorkload(),
          "trace": synthetic_trace(seed=3, n_streams=2, n_steps=24)}
    dp = {"none": None, "static": StaticDispatch(),
          "online": OnlineDispatch(alpha=0.2, prior_weight=5.0),
          "windowed": OnlineDispatch(window=12)}
    dr = {"none": None, "throttle": _drift()}
    cl = {"none": None,
          "tier": CloudTier(rtt_ms=80.0, bw_mbps=float("inf"),
                            payload_kb=np.linspace(30, 90, 5))}
    sc = Scenario(n_users=7, n_requests=90, policy="LT", gamma=0.25,
                  delta=15.0, stickiness=0.7, seed=11, mesh=None,
                  workload=wl[workload], dispatch=dp[dispatch],
                  drift=dr[drift], cloud=cl[cloud])
    back = Scenario.from_json(sc.to_json())
    assert back == sc and back.hash == sc.hash
    again = Scenario.from_json(json.dumps(sc.to_json()))
    assert again == sc
    # spec is canonical: serializing the round-trip changes nothing
    assert back.to_json() == sc.to_json()
    # components restored by VALUE, not reference
    if drift == "throttle":
        np.testing.assert_array_equal(np.asarray(back.drift.t_scale),
                                      np.asarray(sc.drift.t_scale))
    if workload == "trace":
        np.testing.assert_array_equal(np.asarray(back.workload.counts),
                                      np.asarray(sc.workload.counts))
        assert back.workload.name == sc.workload.name
    if cloud == "tier":
        np.testing.assert_array_equal(back.cloud.payload_kb,
                                      sc.cloud.payload_kb)
        assert back.cloud.bw_mbps == float("inf")


def test_roundtripped_scenario_runs_identically():
    """A spec is self-contained: the deserialized scenario (inline trace
    counts, drift arrays, engine hyper-parameters) produces bit-identical
    records to the original objects."""
    sc = Scenario(n_users=5, n_requests=120, seed=2,
                  workload=synthetic_trace(seed=9, n_streams=3,
                                           n_steps=32),
                  dispatch=OnlineDispatch(window=8), drift=_drift())
    back = Scenario.from_json(json.dumps(sc.to_json()))
    a, b = records(sc), records(back)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


def test_scenario_profile_inline_roundtrip_and_hash_sensitivity():
    prof = synthetic_fleet(jax.random.PRNGKey(4), 5)
    sc = Scenario(profile=prof, n_requests=80)
    back = Scenario.from_json(sc.to_json())
    assert back == sc
    np.testing.assert_array_equal(np.asarray(back.resolve_profile().T),
                                  np.asarray(prof.T))
    # the hash actually discriminates scenarios...
    assert sc.hash != Scenario(n_requests=80).hash
    assert Scenario(seed=0).hash != Scenario(seed=1).hash
    # ...but NOT by mesh: sharded runs are bit-identical, so a --sharded
    # artifact stays gateable against the single-device baseline
    assert Scenario(mesh="local").hash == Scenario().hash
    assert Scenario(mesh="local").to_json()["mesh"] == "local"


def test_default_equivalent_components_share_one_spec():
    """An explicit MarkovWorkload()/StaticDispatch() IS the default: the
    spec canonicalizes them to null, so default-equivalent scenarios are
    == with one hash — a hand-written --scenario spec saying
    {"kind": "markov"} gates cleanly against the committed baseline."""
    from repro.core.workload import MarkovWorkload

    assert Scenario(workload=MarkovWorkload()) == Scenario()
    assert Scenario(workload=MarkovWorkload()).hash == Scenario().hash
    assert Scenario(dispatch=StaticDispatch()) == Scenario()
    assert Scenario(dispatch=StaticDispatch()).hash == Scenario().hash
    assert Scenario(workload=MarkovWorkload()).to_json()["workload"] is None
    # the explicit spec forms still parse
    spec = Scenario().to_json()
    spec["workload"] = {"kind": "markov"}
    spec["dispatch"] = {"kind": "static"}
    assert Scenario.from_json(spec) == Scenario()
    # non-default components still discriminate
    assert Scenario(dispatch=OnlineDispatch()).hash != Scenario().hash


def test_scenario_validation():
    with pytest.raises(ValueError, match="unknown profile"):
        Scenario(profile="nope")
    with pytest.raises(ValueError, match="unknown policy"):
        Scenario(policy="XX")
    with pytest.raises(ValueError, match="mesh must be"):
        Scenario(mesh="galaxy")
    with pytest.raises(TypeError, match="profile must be"):
        Scenario(profile=123)
    with pytest.raises(ValueError, match="not a repro-scenario/v1"):
        Scenario.from_json({"schema": "other"})


# --------------------------------------- golden bit-identity (PR 3) ----

def test_scenario_records_bit_identical_to_pr3_golden():
    """records(Scenario(...)) reproduces the pre-DispatchEngine engine's
    records bit for bit — the scenario path IS the engine, not a copy."""
    fix = _golden()
    prof = paper_fleet()
    for entry in fix["records"]:
        sc = Scenario(profile=prof, **entry["config"])
        recs = records(sc)
        assert set(recs) == set(entry["records"])
        for k, v in entry["records"].items():
            np.testing.assert_array_equal(
                np.asarray(recs[k], np.float64), np.asarray(v),
                err_msg=f"{entry['config']}:{k}")


def test_scenario_sweep_bit_identical_to_pr3_golden():
    """run(Scenario, Sweep) over the golden grid == the golden sweep
    metrics, every bit — the named-axis layout maps onto the legacy
    SWEEP_AXES product exactly."""
    fix = _golden()["sweep"]
    res = run(Scenario(profile=paper_fleet(),
                       n_requests=fix["n_requests"]),
              Sweep(policy=tuple(fix["policies"]),
                    n_users=tuple(fix["user_levels"]),
                    seed=tuple(fix["seeds"])))
    assert res.axes == ("policy", "n_users", "seed")
    for k, v in fix["metrics"].items():
        ref = np.asarray(v).reshape(len(fix["policies"]),
                                    len(fix["user_levels"]),
                                    len(fix["seeds"]))
        np.testing.assert_array_equal(res[k], ref, err_msg=k)


@LEGACY_OK
def test_legacy_entry_points_warn_and_match_scenario_path():
    """Every legacy entry point issues LegacyAPIWarning and returns
    bit-identical results to its scenario-path replacement."""
    from repro.core import simulator as SIM

    prof = paper_fleet()
    kw = dict(policies=("MO", "LT"), user_levels=(3, 7), seeds=(0, 1),
              n_requests=150)
    with pytest.warns(LegacyAPIWarning):
        legacy = SIM.sweep_grid(prof, **kw)
    res = run(Scenario(profile=prof, n_requests=150),
              Sweep(policy=("MO", "LT"), n_users=(3, 7), gamma=(0.5,),
                    delta=(20.0,), oracle_estimator=(False,),
                    seed=(0, 1)))
    for k in legacy:
        np.testing.assert_array_equal(legacy[k], res[k], err_msg=k)

    cfg = SimConfig(n_users=4, n_requests=120, seed=5)
    with pytest.warns(LegacyAPIWarning):
        ref = SIM.simulate(prof, cfg)
    out = records(Scenario(profile=prof, n_users=4, n_requests=120,
                           seed=5))
    for k in ref:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(ref[k]), err_msg=k)

    with pytest.warns(LegacyAPIWarning):
        rp = SIM.run_policy(prof, "MO", n_users=4, n_requests=120, seed=5)
    sc = Scenario(profile=prof, n_users=4, n_requests=120, seed=5)
    want = {k: float(v)
            for k, v in summarize(records(sc), prof, sc.to_config()).items()}
    assert rp == want

    with pytest.warns(LegacyAPIWarning):
        grid = SIM.make_grid(prof, [cfg])
    with pytest.warns(LegacyAPIWarning):
        recs = SIM.simulate_batch(prof, grid, n_requests=120)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(recs[k][0]),
                                      np.asarray(ref[k]), err_msg=k)

    with pytest.warns(LegacyAPIWarning):
        sw = SIM.sweep(prof, ["MO"], [3], n_requests=120, seeds=(0, 1))
    np.testing.assert_allclose(
        sw["MO"]["latency_ms"][0],
        run(Scenario(profile=prof, n_requests=120),
            Sweep(policy=("MO",), n_users=(3,),
                  seed=(0, 1))).mean("latency_ms", over="seed")[0, 0])


# ----------------------------------------- new axes, fused programs ----

def test_stickiness_axis_runs_as_one_fused_program(monkeypatch):
    """The acceptance check: an axis OUTSIDE the old SWEEP_AXES tuple
    (stickiness) runs end-to-end through run() as ONE fused device
    program and lands as a named axis of the Results."""
    from repro.core import simulator as SIM

    calls = []
    orig = SIM._sweep_summaries

    def spy(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(SIM, "_sweep_summaries", spy)
    sticks, seeds = (0.5, 0.85, 0.99), (0, 1)
    res = run(Scenario(n_users=6, n_requests=150),
              Sweep(stickiness=sticks, seed=seeds))
    assert len(calls) == 1                     # ONE fused program
    assert res.axes == ("stickiness", "seed")
    assert res.coords["stickiness"] == sticks
    assert res["latency_ms"].shape == (3, 2)
    # each stickiness slice equals its own per-value fused run, bit for
    # bit, and the scalar summarize path agrees to float32 tolerance
    # (vmap may reassociate reductions — same bound as summarize_batch)
    for st in sticks:
        one = run(Scenario(n_users=6, n_requests=150, stickiness=st),
                  Sweep(seed=seeds))
        np.testing.assert_array_equal(res.sel("latency_ms", stickiness=st),
                                      one["latency_ms"])
        for sd in seeds:
            sc = Scenario(n_users=6, n_requests=150, stickiness=st,
                          seed=sd)
            want = summarize(records(sc), paper_fleet(), sc.to_config())
            np.testing.assert_allclose(
                res.sel("latency_ms", stickiness=st, seed=sd),
                np.float64(want["latency_ms"]), rtol=1e-5)
    # varying stickiness genuinely changes the workload
    assert len({res["latency_ms"][i, 0] for i in range(3)}) == 3


def test_drift_axis_fuses_same_shape_schedules(monkeypatch):
    """A drift axis over same-shape schedules becomes one vmapped batch
    axis — no per-value Python loop — and each slice equals the
    per-drift scalar run."""
    from repro.core import simulator as SIM

    calls = []
    orig = SIM._sweep_summaries

    def spy(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(SIM, "_sweep_summaries", spy)
    prof = paper_fleet()
    drifts = tuple(DriftSchedule.throttle(prof, 4, at_step=50, t_mult=tm,
                                          e_mult=2.0)
                   for tm in (1.5, 3.0, 6.0))
    sc = Scenario(profile=prof, n_users=6, n_requests=150)
    seeds = tuple(range(8))
    res = run(sc, Sweep(drift=drifts, seed=seeds))
    assert not calls                           # fused drift path, no loop
    assert res.axes == ("drift", "seed")
    assert res["latency_ms"].shape == (3, len(seeds))
    for d in drifts:
        one = run(replace(sc, drift=d), Sweep(seed=seeds))
        np.testing.assert_array_equal(res.sel("latency_ms", drift=d),
                                      one["latency_ms"], err_msg="drift")
    # severity ordering: harsher throttle of the energy favourite hurts
    # on the seed mean (single closed-loop draws swing by more than the
    # throttle's effect)
    lat = res.mean("latency_ms", over="seed")
    assert lat[2] > lat[0]
    # sel() matches by VALUE, not identity: a schedule rebuilt with the
    # same arguments (or round-tripped through JSON) selects its entry
    rebuilt = DriftSchedule.throttle(prof, 4, at_step=50, t_mult=3.0,
                                     e_mult=2.0)
    np.testing.assert_array_equal(res.sel("latency_ms", drift=rebuilt),
                                  res.sel("latency_ms", drift=drifts[1]))


def test_component_axes_loop_with_named_coords():
    """workload / dispatch axes (different pytree structures) run one
    fused program per value but still land as named axes."""
    tw = synthetic_trace(seed=5, n_streams=3, n_steps=48)
    res = run(Scenario(n_users=4, n_requests=120),
              Sweep(workload=(None, tw),
                    dispatch=(None, OnlineDispatch())))
    assert res.axes == ("workload", "dispatch")
    assert res["latency_ms"].shape == (2, 2)
    base = run(Scenario(n_users=4, n_requests=120))
    np.testing.assert_array_equal(
        res.sel("latency_ms", workload=None, dispatch=None),
        base["latency_ms"])
    tr = run(Scenario(n_users=4, n_requests=120, workload=tw))
    np.testing.assert_array_equal(
        res.sel("latency_ms", workload=tw, dispatch=None),
        tr["latency_ms"])


def test_n_requests_static_axis():
    res = run(Scenario(n_users=3), Sweep(n_requests=(80, 160)))
    assert res.axes == ("n_requests",)
    one = run(Scenario(n_users=3, n_requests=160))
    np.testing.assert_array_equal(res.sel("makespan_s", n_requests=160),
                                  one["makespan_s"])


def test_profile_axis_stacks_same_shape_fleets():
    fleets = [synthetic_fleet(jax.random.PRNGKey(i), 5) for i in range(2)]
    res = run(Scenario(n_users=4, n_requests=120),
              Sweep(seed=(0, 1), profile=tuple(fleets)))
    assert res.axes == ("seed", "profile")
    for f, fleet in enumerate(fleets):
        one = run(Scenario(profile=fleet, n_users=4, n_requests=120),
                  Sweep(seed=(0, 1)))
        np.testing.assert_array_equal(
            res.sel("latency_ms", profile=fleets[f]), one["latency_ms"])


def test_ragged_profile_axis_overrides_stacked_base():
    """A profile axis of differing shapes loops (no stacking) and fully
    replaces the scenario's own profile — even a stacked one: no phantom
    implicit fleet axis, and each slice equals that fleet's own run."""
    ragged = (synthetic_fleet(jax.random.PRNGKey(0), 4),
              synthetic_fleet(jax.random.PRNGKey(1), 6))
    base = stack_profiles([paper_fleet(), paper_fleet()])
    res = run(Scenario(profile=base, n_users=3, n_requests=80),
              Sweep(profile=ragged, seed=(0, 1)))
    assert res.axes == ("profile", "seed")
    assert res["latency_ms"].shape == (2, 2)
    for fleet in ragged:
        one = run(Scenario(profile=fleet, n_users=3, n_requests=80),
                  Sweep(seed=(0, 1)))
        np.testing.assert_array_equal(res.sel("latency_ms", profile=fleet),
                                      one["latency_ms"])


def test_stacked_profile_adds_named_fleet_axis():
    ens = stack_profiles([synthetic_fleet(jax.random.PRNGKey(i), 5)
                          for i in range(3)])
    res = run(Scenario(profile=ens, n_users=4, n_requests=100),
              Sweep(policy=("MO", "LT")))
    assert res.axes == ("fleet", "policy")
    assert res["latency_ms"].shape == (3, 2)
    assert res.coords["fleet"] == (0, 1, 2)


def test_mesh_spec_is_bit_identical_to_single_device():
    sw = Sweep(policy=("MO", "LT"), n_users=(3, 7), seed=(0,))
    ref = run(Scenario(n_requests=120), sw)
    out = run(Scenario(n_requests=120, mesh="local"), sw)
    for k in ref.metric_names:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


# --------------------------------------------------- records batched ----

def test_records_batched_rows_equal_single_runs():
    sc = Scenario(n_users=5, n_requests=120)
    sweep = Sweep(policy=("MO", "RR"), seed=(0, 1, 2))
    recs = records(sc, sweep)
    assert recs["latency"].shape == (2, 3, 120)
    for pi, pol in enumerate(("MO", "RR")):
        for si in range(3):
            one = records(replace(sc, policy=pol, seed=si))
            for k in one:
                np.testing.assert_array_equal(
                    np.asarray(recs[k][pi, si]), np.asarray(one[k]),
                    err_msg=f"{pol}/s{si}:{k}")


def test_records_rejects_component_axes():
    with pytest.raises(ValueError, match="config-leaf axes only"):
        records(Scenario(), Sweep(dispatch=(None, OnlineDispatch())))


# ----------------------------------------------- Sweep / Results API ----

def test_sweep_validation_and_scalars():
    with pytest.raises(ValueError, match="unknown sweep axis"):
        Sweep(users=(3,))
    with pytest.raises(ValueError, match="unknown sweep axis"):
        Sweep(mesh=("local",))
    with pytest.raises(ValueError, match="no values"):
        Sweep(seed=())
    sw = Sweep(policy="MO", seed=range(2))     # scalars + ranges coerce
    assert sw.names == ("policy", "seed")
    assert sw.values("policy") == ("MO",) and sw.shape == (1, 2)
    with pytest.raises(KeyError):
        sw.values("gamma")
    assert Sweep(seed=(0, 1)) == Sweep(seed=[0, 1])


def test_results_sel_mean_scalar_errors():
    res = run(Scenario(n_users=3, n_requests=100),
              Sweep(policy=("MO", "LT"), seed=(0, 1)))
    assert isinstance(res, Results)
    with pytest.raises(KeyError, match="no axis"):
        res.sel("latency_ms", gamma=0.5)
    with pytest.raises(KeyError, match="not on axis"):
        res.sel("latency_ms", policy="HA")
    with pytest.raises(ValueError, match="use sel"):
        res.scalar("latency_ms")
    assert res.mean("latency_ms", over="seed").shape == (2,)
    assert res.mean("latency_ms", over=("policy", "seed")).shape == ()
    scalar = run(Scenario(n_users=3, n_requests=100))
    assert scalar.shape == () and scalar.scalar("map") > 0
    assert "Results" in repr(res) and "policy" in repr(res)


def test_profile_registry_extensible():
    SC.register_profile("tiny-test",
                        lambda: synthetic_fleet(jax.random.PRNGKey(0), 4))
    try:
        sc = Scenario(profile="tiny-test", n_users=3, n_requests=80)
        assert sc.resolve_profile().n_pairs == 4
        assert Scenario.from_json(sc.to_json()) == sc    # by name
        assert run(sc).scalar("latency_ms") > 0
    finally:
        del SC.PROFILE_REGISTRY["tiny-test"]


# ------------------------------------------------- serving gateway ----

def test_gateway_accepts_scenario():
    """WindowedGateway(scenario) adopts the scenario's profile, policy,
    gamma, delta, seed and dispatch engine — sim and serving share ONE
    config object (the deprecated per-request Gateway shim inherits the
    identical resolution; tests/test_serving_plane.py pins the shim)."""
    from repro.serving.gateway import WindowedGateway

    sc = Scenario(policy="LT", gamma=0.75, delta=5.0, seed=7,
                  dispatch=OnlineDispatch(window=4))
    gw = WindowedGateway(sc)
    assert gw.policy == "LT" and gw.gamma == 0.75 and gw.delta == 5.0
    assert gw.seed == 7 and gw.dispatch == OnlineDispatch(window=4)
    assert gw.online is True       # any OnlineDispatch flavour counts
    np.testing.assert_array_equal(
        np.asarray(gw.prof.T), np.asarray(sc.resolve_profile().T))
    # identical decisions to the kwarg-built gateway
    ref = WindowedGateway(paper_fleet(), policy="LT", gamma=0.75,
                          delta=5.0, seed=7,
                          dispatch=OnlineDispatch(window=4))
    q = np.zeros(5, np.float32)
    np.testing.assert_array_equal(
        np.asarray(gw.route_window(range(4), q)[0]),
        np.asarray(ref.route_window(range(4), q)[0]))
    with pytest.raises(ValueError, match="stacked"):
        WindowedGateway(Scenario(profile=stack_profiles(
            [paper_fleet(), paper_fleet()])))
    # a redundant online=True must NOT swap the scenario's tuned engine
    # for a default OnlineDispatch(); it only fills in when the scenario
    # left dispatch unset
    tuned = WindowedGateway(sc, online=True)
    assert tuned.dispatch == OnlineDispatch(window=4)
    bare = WindowedGateway(Scenario(), online=True)
    assert bare.dispatch == OnlineDispatch()
    # explicitly passed non-default knobs win over the scenario (tweak
    # one knob on a shared spec); untouched knobs adopt the scenario's
    tweaked = WindowedGateway(sc, policy="HA", gamma=0.9)
    assert tweaked.policy == "HA" and tweaked.gamma == 0.9
    assert tweaked.delta == 5.0 and tweaked.seed == 7
