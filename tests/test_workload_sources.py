"""The workload-source contract (ISSUE 3): the Markov path through the
``WorkloadSource`` interface reproduces the PR 2 engine bit for bit, the
trace path reproduces an independent looped NumPy replay bit for bit, and
the contract's invariants hold property-based.

The golden fixture (``golden_markov_pr2.json``) holds the values of the
engine from before ``WorkloadSource`` existed, recaptured for the
installed JAX release by ``scripts/capture_golden_engine.py`` from code
that matched that engine bit for bit — do not regenerate it from the code
under test, that would defeat the regression.
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.estimator import group_of_count, markov_transition
from repro.core.policies import POLICY_CODES
from repro.core.profiles import paper_fleet
from repro.core.scenario import Scenario, Sweep, records, run
from repro.core.simulator import (SimConfig, _make_grid, _simulate_batch,
                                  summarize)
from repro.core.workload import MarkovWorkload, default_workload
from repro.data.traces import (TraceWorkload, bundled_trace, load_trace,
                               save_trace, synthetic_trace)

GOLDEN = Path(__file__).resolve().parent / "golden_markov_pr2.json"

f4 = np.float32
BIG = f4(1e30)


def _golden():
    with open(GOLDEN) as f:
        return json.load(f)


# ------------------------------------------------ Markov bit-identity --

def test_markov_records_bit_identical_to_pr2_golden():
    """The engine through the WorkloadSource interface (scenario path) ==
    the records the pre-interface engine produced, every field, every
    bit."""
    fix = _golden()
    prof = paper_fleet()
    for entry in fix["records"]:
        recs = records(Scenario(profile=prof, **entry["config"]))
        assert set(recs) == set(entry["records"])
        for k, v in entry["records"].items():
            np.testing.assert_array_equal(
                np.asarray(recs[k], np.float64), np.asarray(v), err_msg=k)


def test_markov_sweep_bit_identical_to_pr2_golden():
    fix = _golden()["sweep"]
    res = run(Scenario(n_requests=fix["n_requests"]),
              Sweep(policy=tuple(fix["policies"]),
                    n_users=tuple(fix["user_levels"]),
                    seed=tuple(fix["seeds"])))
    for k, v in fix["metrics"].items():
        want = np.asarray(v).reshape(res[k].shape)
        np.testing.assert_array_equal(res[k], want, err_msg=k)


def test_explicit_markov_workload_matches_default():
    """Passing MarkovWorkload() explicitly is the default path."""
    sc = Scenario(n_users=4, n_requests=150, policy="MO", seed=7)
    ref = records(sc)
    out = records(Scenario(n_users=4, n_requests=150, policy="MO", seed=7,
                           workload=MarkovWorkload()))
    for k in ref:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)
    assert isinstance(default_workload(), MarkovWorkload)


# ------------------------------------- trace replay (NumPy reference) --

def _np_trace_replay(prof, cfg: SimConfig, tw: TraceWorkload,
                     n_users_max: int):
    """Looped NumPy reimplementation of the closed-loop simulator driven
    by a trace. Valid for oracle configs with RNG-free policies (MO, RR,
    LC, LT): the trace supplies every count, so no threefry draw feeds
    any record and plain float32 NumPy reproduces the scan bit for bit."""
    T = np.asarray(prof.T, f4)
    E = np.asarray(prof.E, f4)
    MAP = np.asarray(prof.mAP, f4)
    P, G = T.shape
    counts = np.asarray(tw.counts)
    S, TL = counts.shape
    true0, _rng, phase = tw.init_draws(cfg.seed, cfg.stickiness,
                                       n_groups=G, n_users=cfg.n_users)
    U = n_users_max
    gamma, delta = f4(cfg.gamma), f4(cfg.delta)
    assert cfg.oracle_estimator and cfg.policy in ("MO", "RR", "LC", "LT")

    t_next = np.where(np.arange(U) < cfg.n_users,
                      np.arange(U, dtype=f4) * f4(1e-4), f4(np.inf))
    t_next = t_next.astype(f4)
    true_cnt = np.zeros((U,), np.int32)
    true_cnt[:cfg.n_users] = true0
    ph = np.zeros((U,), np.int32)
    ph[:cfg.n_users] = phase
    pos = np.zeros((U,), np.int64)
    server = np.full((U,), -1, np.int64)
    finish_by_user = np.zeros((U,), f4)
    avail = np.zeros((P,), f4)
    rr = 0

    out = {k: [] for k in ("t_arrival", "latency", "energy", "map",
                           "server", "g_true", "g_est", "q_at_dispatch",
                           "correct_group")}
    for _ in range(cfg.n_requests):
        u = int(np.argmin(t_next))
        t = t_next[u]
        new_true = int(counts[u % S, (ph[u] + pos[u] + 1) % TL])
        g = int(np.clip(new_true, 0, G - 1))

        q = np.zeros((P,), f4)
        for v in range(U):
            if finish_by_user[v] > t and server[v] >= 0:
                q[server[v]] += f4(1.0)

        if cfg.policy == "MO":
            map_max = MAP[:, g].max()
            feas = MAP[:, g] >= map_max - delta
            L_exp = T[:, g] * (f4(1.0) + q)
            l_min = np.where(feas, L_exp, BIG).min()
            l_max = np.where(feas, L_exp, -BIG).max()
            e_min = np.where(feas, E[:, g], BIG).min()
            e_max = np.where(feas, E[:, g], -BIG).max()
            L_n = (L_exp - l_min) / np.maximum(l_max - l_min, f4(1e-9))
            E_n = (E[:, g] - e_min) / np.maximum(e_max - e_min, f4(1e-9))
            J = gamma * L_n + (f4(1.0) - gamma) * E_n
            scores = np.where(feas, J, BIG)
        elif cfg.policy == "RR":
            scores = ((np.arange(P) - rr % P) % P).astype(f4)
        elif cfg.policy == "LC":
            scores = q
        else:                                  # LT
            scores = T[:, g] * (f4(1.0) + q)
        p = int(np.argmin(scores))

        # XLA compiles the scan's ms->s conversion + add as a fused
        # multiply-add by the f32 reciprocal of the constant divisor:
        # finish = fma(T, 1/1000, start), ONE rounding. The f64 detour
        # reproduces that single rounding (the f32xf32 product is exact
        # in f64); a plain f32 mult-then-add drifts 1 ULP.
        recip = np.float64(f4(f4(1.0) / f4(1000.0)))
        start = np.maximum(t, avail[p])
        fin = f4(np.float64(start) + np.float64(T[p, g]) * recip)

        out["t_arrival"].append(t)
        out["latency"].append(f4(fin - t))
        out["energy"].append(E[p, g])
        out["map"].append(MAP[p, g])
        out["server"].append(p)
        out["g_true"].append(g)
        out["g_est"].append(g)                 # oracle: g_est == g_true
        out["q_at_dispatch"].append(q[p])
        out["correct_group"].append(f4(1.0))

        true_cnt[u] = new_true
        pos[u] += 1
        server[u] = p
        finish_by_user[u] = fin
        avail[p] = fin
        t_next[u] = fin
        rr += 1
    return {k: np.asarray(v) for k, v in out.items()}


def test_trace_records_bit_identical_to_numpy_replay():
    """The acceptance check: a trace-driven policy × users × seed grid run
    as ONE jitted vmapped scan reproduces, row by row and bit by bit, an
    independent looped NumPy replay of the same traces."""
    prof = paper_fleet()
    tw = bundled_trace()
    cfgs = [SimConfig(n_users=u, n_requests=160, policy=p, seed=s,
                      oracle_estimator=True)
            for p in ("MO", "RR", "LC", "LT")
            for u in (3, 7) for s in (0, 1)]
    grid = _make_grid(prof, cfgs, workload=tw)
    recs = _simulate_batch(prof, grid, n_requests=160, workload=tw)
    for i, cfg in enumerate(cfgs):
        ref = _np_trace_replay(prof, cfg, tw, grid.n_users_max)
        for k, v in ref.items():
            np.testing.assert_array_equal(
                np.asarray(recs[k][i], v.dtype), v,
                err_msg=f"{cfg.policy}/u{cfg.n_users}/s{cfg.seed}:{k}")


def test_trace_sweep_matches_replayed_metrics():
    """The fused summaries over a trace grid equal the engine summarizer
    applied to the NumPy-replayed records (float32-tight)."""
    prof = paper_fleet()
    tw = bundled_trace()
    pols, users, seeds = ("MO", "LT"), (3, 7), (0, 1)
    m = run(Scenario(workload=tw, n_requests=160, oracle_estimator=True),
            Sweep(policy=pols, n_users=users, seed=seeds))
    for pol in pols:
        for u in users:
            for s in seeds:
                cfg = SimConfig(n_users=u, n_requests=160, policy=pol,
                                seed=s, oracle_estimator=True)
                ref = _np_trace_replay(prof, cfg, tw, max(users))
                want = summarize({k: jax.numpy.asarray(v)
                                  for k, v in ref.items()}, prof, cfg)
                for k, v in want.items():
                    np.testing.assert_allclose(
                        m.sel(k, policy=pol, n_users=u, seed=s),
                        float(v), rtol=1e-5,
                        err_msg=f"{pol}/u{u}/s{s}:{k}")


def test_trace_single_equals_batched_row():
    """Padding/batching invariance holds for traces exactly as for the
    Markov source: each row of a mixed-n_users batch equals its own
    unpadded single run."""
    prof = paper_fleet()
    tw = synthetic_trace(seed=5, n_streams=4, n_steps=64)
    cfgs = [SimConfig(n_users=u, n_requests=200, policy="MO", seed=u,
                      workload=tw) for u in (2, 6, 11)]
    grid = _make_grid(prof, cfgs)
    recs = _simulate_batch(prof, grid, n_requests=200, workload=tw)
    for i, cfg in enumerate(cfgs):
        ref = records(Scenario(workload=tw, n_users=cfg.n_users,
                               n_requests=200, policy="MO",
                               seed=cfg.seed))
        for k in ref:
            np.testing.assert_array_equal(np.asarray(recs[k][i]),
                                          np.asarray(ref[k]), err_msg=k)


# ------------------------------------------------- contract properties --

@settings(max_examples=30, deadline=None)
@given(st.integers(2, 12), st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_markov_transition_rows_exactly_stochastic(n, stick, drift_up):
    """Rows renormalise to exactly 1 (float32) and stay non-negative over
    the whole parameter cube, boundary values included."""
    P = np.asarray(markov_transition(n, stick, drift_up))
    np.testing.assert_allclose(P.sum(1), 1.0, atol=2e-6)
    assert (P >= 0).all()


@settings(max_examples=30, deadline=None)
@given(st.integers(-5, 40), st.integers(1, 9))
def test_group_of_count_clips_into_range(count, n_groups):
    g = int(group_of_count(np.int32(count), n_groups))
    assert 0 <= g <= n_groups - 1
    if 0 <= count < n_groups:
        assert g == count


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6), st.integers(2, 50),
       st.integers(1, 9))
def test_trace_workload_groups_always_in_range(seed, n_streams, n_steps,
                                               n_users):
    """For arbitrary trace shapes, seeds and per-user offsets, every count
    a TraceWorkload emits maps into group range [0, n_groups-1] — the
    initial draw and any number of steps."""
    n_groups = 5
    rng = np.random.default_rng(seed)
    tw = TraceWorkload(rng.integers(0, 12, size=(n_streams, n_steps)))
    true0, _, phase = tw.init_draws(seed, 0.85, n_groups=n_groups,
                                    n_users=n_users)
    assert true0.shape == (n_users,) and phase.shape == (n_users,)
    assert ((phase >= 0) & (phase < n_steps)).all()
    ctx = tw.prepare(n_groups, 0.85)
    for u in range(n_users):
        for k in range(n_steps + 3):           # wraps past the trace end
            c = int(tw.next_count(ctx, None, None, np.int32(u),
                                  np.int32(phase[u] + k)))
            g = int(group_of_count(np.int32(c), n_groups))
            assert 0 <= g <= n_groups - 1
            if k == 0:
                assert c == int(true0[u])


# ------------------------------------------------------ traces plumbing --

def test_trace_roundtrip_and_loader_errors(tmp_path):
    tw = synthetic_trace(seed=3, n_streams=3, n_steps=40)
    p = tmp_path / "t.npz"
    save_trace(p, tw)
    back = load_trace(p)
    assert back.name == tw.name
    np.testing.assert_array_equal(np.asarray(back.counts),
                                  np.asarray(tw.counts))
    np.savez(tmp_path / "bad.npz", other=np.arange(3))
    with pytest.raises(ValueError, match="no 'counts'"):
        load_trace(tmp_path / "bad.npz")
    with pytest.raises(ValueError, match="negative"):
        TraceWorkload(np.array([[1, -2, 3]]))
    with pytest.raises(ValueError, match="counts must be"):
        TraceWorkload(np.zeros((2, 2, 2), np.int32))
    one_d = TraceWorkload(np.arange(6))
    assert one_d.n_streams == 1 and one_d.length == 6


def test_synthetic_trace_busy_crossing_statistics():
    """The CI generator is seeded-deterministic and carries the paper's
    busy-crossing skew: complex scenes (group 3+) outnumber empty ones,
    and the 4+ group is realised as 4..max_count objects."""
    a = synthetic_trace(seed=11, n_streams=6, n_steps=400)
    b = synthetic_trace(seed=11, n_streams=6, n_steps=400)
    np.testing.assert_array_equal(np.asarray(a.counts),
                                  np.asarray(b.counts))
    c = np.asarray(a.counts)
    assert c.min() >= 0 and c.max() <= 7
    groups = np.clip(c, 0, 4)
    assert (groups == 3).mean() > (groups == 0).mean()
    assert (c >= 4).any()                      # the open-ended bucket


def test_simulate_batch_rejects_trace_grid_under_markov_default():
    """Forgetting to repeat workload= on a trace-built grid must raise,
    not silently Markov-step from trace-drawn initial states."""
    prof = paper_fleet()
    tw = bundled_trace()
    cfgs = [SimConfig(n_users=5, n_requests=50, seed=0)]
    grid = _make_grid(prof, cfgs, workload=tw)
    with pytest.raises(ValueError, match="nonzero workload phase"):
        _simulate_batch(prof, grid, n_requests=50)
    _simulate_batch(prof, grid, n_requests=50, workload=tw)  # correct call
    markov_grid = _make_grid(prof, cfgs)
    _simulate_batch(prof, markov_grid, n_requests=50)        # default fine


def test_grid_rejects_mixed_workload_sources():
    prof = paper_fleet()
    t1 = synthetic_trace(seed=1, n_streams=2, n_steps=16)
    t2 = synthetic_trace(seed=2, n_streams=2, n_steps=16)
    cfgs = [SimConfig(n_users=3, n_requests=50, workload=t1),
            SimConfig(n_users=3, n_requests=50, workload=t2)]
    with pytest.raises(ValueError, match="share a single workload"):
        _make_grid(prof, cfgs)
    with pytest.raises(ValueError, match="conflicts"):
        _make_grid(prof, cfgs[:1], workload=t2)
    grid = _make_grid(prof, cfgs[:1])          # cfg-carried source works
    assert grid.phase.shape == (1, 3)


def test_trace_init_draws_memoized_and_deterministic():
    tw = bundled_trace()
    a = tw.init_draws(4, 0.85, n_groups=5, n_users=6)
    b = tw.init_draws(4, 0.5, n_groups=5, n_users=6)   # stickiness ignored
    assert a[0] is b[0]                        # per-instance memo hit
    fresh = bundled_trace().init_draws(4, 0.85, n_groups=5, n_users=6)
    for x, y in zip(a, fresh):
        np.testing.assert_array_equal(x, y)
    t0, _, phase = a
    np.testing.assert_array_equal(
        t0, np.asarray(tw.counts)[np.arange(6) % tw.n_streams, phase])


def test_sim_config_with_trace_stays_hashable():
    """SimConfig must stay usable in sets/dicts with any workload source
    attached (the workload is compare-excluded grid data)."""
    tw = bundled_trace()
    a = SimConfig(n_users=3, workload=tw)
    b = SimConfig(n_users=3)
    assert hash(a) == hash(b) and a == b
    assert len({a, b}) == 1
    assert POLICY_CODES[a.policy] == 0
