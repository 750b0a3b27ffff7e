"""The user axis at scale (ISSUE 7): block decomposition, left-fold
segment aggregation, streamed workload draws, and the 10^5-user
acceptance run.

Pinned contracts:
  * segment-reduced per-user aggregation is BIT-equal to the dense
    masked reduction (property-tested, incl. all-padded and single-user
    rows) — both are the same left fold in index order, the thing a
    plain ``where(mask).sum(-1)`` is not;
  * every n_users <= user_block scenario is bit-identical to the
    un-blocked engine and to the PR 2/PR 3 golden fixtures (single
    device AND a forced 4-device mesh — fixtures are pinned, never
    regenerated);
  * streamed (chunked) workload draws reassemble bitwise for any chunk
    size, Markov and trace both;
  * a multi-block config's metrics equal the left-fold combination of
    its blocks run one-by-one — with ``latency_p90_ms`` the exact
    percentile of the merged per-block latency histograms
    (partition-invariant: any K-way block split of the same pooled
    sample gives the identical merged histogram, hence the identical
    percentile);
  * one ``run()`` at n_users=10^5 completes on CPU with users/sec >=
    10x the looped per-value (dense-user) path; 10^6 runs behind
    ``REPRO_MILLION_USERS=1``.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import useraxis as UA
from repro.core.dispatch import StaticDispatch
from repro.core.profiles import paper_fleet
from repro.core.scenario import (STATIC_AXES, Scenario, Sweep, records,
                                 run)
from repro.core.simulator import (ConfigGrid, SimConfig,
                                  _expand_user_blocks, _make_user_grid,
                                  _sweep_summaries)
from repro.core.workload import MarkovWorkload
from repro.data.traces import bundled_trace

REPO = Path(__file__).resolve().parent.parent
GOLDEN_STATIC = REPO / "tests" / "golden_static_pr3.json"
GOLDEN_MARKOV = REPO / "tests" / "golden_markov_pr2.json"


def _assert_metric_equal(k, out, ref, err_msg=""):
    """Bit-equality, except ``latency_p90_ms`` across DIFFERENT compiled
    batch shapes gets the repo's 1-ULP tolerance (percentile
    interpolation is an FMA-contraction candidate; see
    tests/test_dispatch.py:_assert_metrics_equal)."""
    if k == "latency_p90_ms":
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-7, err_msg=err_msg or k)
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=err_msg or k)


# --------------------------------------------- block decomposition ------

def test_block_decomposition_helpers():
    assert UA.n_user_blocks(15, 1024) == 1
    assert UA.n_user_blocks(1024, 1024) == 1
    assert UA.n_user_blocks(1025, 1024) == 2
    assert UA.block_sizes(2500, 1024) == [1024, 1024, 452]
    assert UA.block_sizes(7, 16) == [7]
    np.testing.assert_array_equal(UA.block_segments([1, 3, 1]),
                                  [0, 1, 1, 1, 2])
    with pytest.raises(ValueError):
        UA.n_user_blocks(10, 0)

    rows, seg = _expand_user_blocks(
        [SimConfig(n_users=5), SimConfig(n_users=20)], 8)
    assert rows == [(0, 0, 5), (1, 0, 8), (1, 1, 8), (1, 2, 4)]
    np.testing.assert_array_equal(seg, [0, 1, 1, 1])


# ------------------------------- segment == dense masked, bitwise -------

@given(st.integers(1, 8), st.integers(1, 32),
       st.integers(0, 2**31 - 1))
@settings(deadline=None, max_examples=25)
def test_segment_reduction_bit_equal_to_dense_masked(b, u, seed):
    """The padded-dense masked reduction, the ragged-flat segment
    reduction and a sequential NumPy left fold agree BITWISE on random
    (n_users, n_users_max) shapes — including all-padded rows (forced on
    row 0) and single-user rows (forced on row 1)."""
    rng = np.random.default_rng(seed)
    n_users = rng.integers(0, u + 1, size=b).astype(np.int32)
    n_users[0] = 0                        # all-padded edge case
    if b > 1:
        n_users[1] = 1                    # single-user edge case
    scale = rng.choice([1.0, 1e-6, 1e6], size=(b, u))
    values = (rng.uniform(-1e3, 1e3, size=(b, u)) * scale) \
        .astype(np.float32)

    dense = np.asarray(UA.masked_user_sum(values, n_users))
    flat_v = np.concatenate(
        [values[i, :n_users[i]] for i in range(b)]) \
        if n_users.any() else np.zeros((0,), np.float32)
    flat_s = np.concatenate(
        [np.full(n_users[i], i, np.int32) for i in range(b)]) \
        if n_users.any() else np.zeros((0,), np.int32)
    ragged = np.asarray(UA.segment_user_sum(flat_v, flat_s, b))
    np.testing.assert_array_equal(dense, ragged)

    ref = np.zeros((b,), np.float32)      # sequential left fold
    for i in range(b):
        acc = np.float32(0.0)
        for j in range(int(n_users[i])):
            acc = np.float32(acc + values[i, j])
        ref[i] = acc
    np.testing.assert_array_equal(dense, ref)

    # means agree the same way (all-padded rows give 0, not NaN)
    dmean = np.asarray(UA.masked_user_mean(values, n_users))
    rmean = np.asarray(UA.segment_user_mean(flat_v, flat_s, b))
    np.testing.assert_array_equal(dmean, rmean)
    assert dmean[0] == 0.0
    if b > 1:                             # single element: exact identity
        assert dmean[1] == values[1, 0]


def test_segment_reduction_eager_equals_jit():
    rng = np.random.default_rng(7)
    v = rng.uniform(-1e3, 1e3, size=(5, 9)).astype(np.float32)
    n = np.asarray([0, 1, 9, 4, 7], np.int32)
    eager = np.asarray(UA.masked_user_sum(v, n))
    jitted = np.asarray(jax.jit(UA.masked_user_sum)(v, n))
    np.testing.assert_array_equal(eager, jitted)


# ------------------------------------ streamed draws: chunk invariance --

@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_streamed_draws_chunk_invariant(chunk):
    """Chunked Markov draws and chunked trace gathers reassemble bitwise
    to the one-shot full-width streamed path for every chunk size —
    per-user fold_in keys make the draw independent of how the user axis
    is partitioned."""
    for wl in (MarkovWorkload(), bundled_trace()):
        ref = wl.stream_draws(3, 0.85, n_groups=5, n_users=100,
                              chunk=100)
        got = wl.stream_draws(3, 0.85, n_groups=5, n_users=100,
                              chunk=chunk)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=type(wl).__name__)


def test_stream_key_matches_legacy_scan_key():
    """The streamed path's scan key is the same per-seed threefry key the
    one-shot init_draws returns, so K=1 and K>1 configs share one
    in-scan RNG convention."""
    for wl in (MarkovWorkload(), bundled_trace()):
        _, rng, _ = wl.init_draws(11, 0.85, n_groups=5, n_users=4)
        np.testing.assert_array_equal(np.asarray(rng), wl.stream_key(11))


# -------------------------------------------- grid build + memory -------

def test_1e5_user_grid_build_under_memory_ceiling():
    """A mixed grid with a 10^5-user config builds with O(total_users)
    leaf bytes (array-size accounting — RSS is too noisy to gate): the
    blocked layout never pads small configs to the big config's width."""
    prof = paper_fleet()
    cfgs = [SimConfig(n_users=15, n_requests=64, seed=s)
            for s in range(24)]
    cfgs.append(SimConfig(n_users=100_000, n_requests=64, seed=99))
    grid, seg = _make_user_grid(prof, cfgs, 1024, chunk=4096)

    rows = 24 + UA.n_user_blocks(100_000, 1024)
    assert grid.n_configs == rows
    assert grid.n_users_max == 1024
    assert int(seg[-1]) == len(cfgs) - 1

    nbytes = UA.grid_nbytes(grid)
    # the dense layout pads every config to n_users_max=10^5: two
    # (25, 100000) int32 leaves alone are 20 MB
    dense_true0_phase = len(cfgs) * 100_000 * 4 * 2
    assert nbytes < dense_true0_phase / 10, nbytes
    # absolute ceiling: ~bytes per padded user slot across block rows
    assert nbytes < 12 * rows * 1024, nbytes


def test_trace_user_block_must_divide_streams():
    """Block-local stream assignment must match the global u % S — only
    user_block multiples of the trace's stream count are coherent."""
    tr = bundled_trace()                          # 8 streams
    sc = Scenario(workload=tr, n_users=40, n_requests=50, user_block=7)
    with pytest.raises(ValueError, match="multiple"):
        run(sc)
    res = run(Scenario(workload=tr, n_users=40, n_requests=50,
                       user_block=8))
    assert np.isfinite(res.scalar("latency_ms"))


# --------------------------------------- K = 1 bit-identity (golden) ----

def test_user_block_records_bit_identical_to_pr3_golden():
    """records() with user_block set (but n_users <= user_block) is the
    IDENTICAL program: every pinned PR 3 record, every field, every
    bit."""
    with open(GOLDEN_STATIC) as f:
        fix = json.load(f)
    for entry in fix["records"]:
        recs = records(Scenario(**entry["config"], user_block=16))
        assert set(recs) >= set(entry["records"])
        for k, v in entry["records"].items():
            np.testing.assert_array_equal(
                np.asarray(recs[k], np.float64), np.asarray(v),
                err_msg=f"{entry['config']}:{k}")


@pytest.mark.parametrize("golden", [GOLDEN_STATIC, GOLDEN_MARKOV],
                         ids=["static_pr3", "markov_pr2"])
def test_user_block_sweep_bit_identical_to_golden(golden):
    """The scenario sweep with user_block=16 (every config K=1)
    reproduces both golden fixtures' metrics bit for bit — block
    expansion and segment aggregation are exact passthroughs at K=1."""
    with open(golden) as f:
        fix = json.load(f)["sweep"]
    res = run(Scenario(n_requests=fix["n_requests"], user_block=16),
              Sweep(policy=tuple(fix["policies"]),
                    n_users=tuple(fix["user_levels"]),
                    seed=tuple(fix["seeds"])))
    for k, v in fix["metrics"].items():
        want = np.asarray(v).reshape(res[k].shape)
        _assert_metric_equal(k, res[k], want)


_SUBPROC_CHECK = """
import json
import jax, numpy as np
from repro.core.scenario import Scenario, Sweep, run

assert len(jax.devices()) == 4, jax.devices()

# K=1 golden bit-identity on a real 4-device mesh, user_block set
fix = json.load(open({golden!r}))["sweep"]
gold = run(Scenario(n_requests=fix["n_requests"], user_block=16,
                    mesh="local"),
           Sweep(policy=tuple(fix["policies"]),
                 n_users=tuple(fix["user_levels"]),
                 seed=tuple(fix["seeds"])))
for k, v in fix["metrics"].items():
    want = np.asarray(v).reshape(gold[k].shape)
    if k == "latency_p90_ms":      # FMA drift across batch shapes
        np.testing.assert_allclose(gold[k], want, rtol=3e-7, err_msg=k)
    else:
        np.testing.assert_array_equal(gold[k], want, err_msg=k)

# multi-block sharded == multi-block single-device, bitwise: block rows
# ride the sharded config axis (per-user state sharded across devices);
# the 500-block grid spans every shard, so its per-config folds would
# reassociate if they ran over the sharded axis
for sc, sw in ((Scenario(n_users=50, n_requests=100, user_block=8), None),
               (Scenario(n_users=2000, n_requests=100, user_block=8),
                Sweep(policy=("MO", "HA")))):
    ref = run(sc, sw)
    out = run(sc, sw, mesh="local")
    for k in ref.metric_names:
        if k == "latency_p90_ms":
            np.testing.assert_allclose(out[k], ref[k], rtol=3e-7, err_msg=k)
        else:
            np.testing.assert_array_equal(out[k], ref[k], err_msg=k)
print("OK")
"""


def test_user_block_bitwise_in_forced_4_device_subprocess():
    """Real multi-device bit-exactness for the user axis, via
    xla_force_host_platform_device_count=4 in a fresh process: K=1 golden
    metrics survive a 4-device mesh with user_block set, and a K>1
    sharded run equals its single-device self bit for bit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    src = _SUBPROC_CHECK.format(golden=str(GOLDEN_MARKOV))
    res = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


_SUBPROC_MERGE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import useraxis as UA
from repro.core.dispatch import StaticDispatch
from repro.core.profiles import paper_fleet, stack_profiles, synthetic_fleet
from repro.core.scenario import Scenario, Sweep, run
from repro.core.simulator import (SimConfig, _make_user_grid,
                                  _simulate_vmapped, _sweep_summaries)
from repro.core.workload import MarkovWorkload
from repro.launch.mesh import make_sweep_mesh

assert len(jax.devices()) == 4, jax.devices()
mesh = make_sweep_mesh()
N_REQ, BLOCK, USERS = 60, 4, (28, 20, 33)      # 7, 5 and 9 blocks
WARMUP = int(N_REQ * 0.1)
wl, de = MarkovWorkload(), StaticDispatch()


def parent_p90(prof, grid, seg, n_cfg):
    # the merge as a fold after the run: per-row histogram, segment sum
    # over the block rows, percentile of the merged counts
    lat = _simulate_vmapped(prof, wl, de, None, None, None, grid,
                            n_requests=N_REQ)["latency"][..., WARMUP:]
    rows = jax.vmap(UA.latency_histogram)
    if prof.is_stacked:
        rows = jax.vmap(rows)
    hist = jnp.moveaxis(rows(lat), -2, 0)
    merged = jnp.moveaxis(UA.segment_user_sum(hist, seg, n_cfg), 0, -2)
    return 1000.0 * UA.histogram_p90(merged)


for prof in (paper_fleet(), stack_profiles(
        [paper_fleet(), synthetic_fleet(jax.random.PRNGKey(1), 5)])):
    sc = Scenario(profile=prof, n_requests=N_REQ, user_block=BLOCK)
    sw = Sweep(n_users=USERS)
    one = run(sc, sw)
    four = run(sc, sw, mesh="local")
    cfgs = [SimConfig(n_users=n, n_requests=N_REQ) for n in USERS]
    grid, seg = _make_user_grid(prof, cfgs, BLOCK)
    assert grid.n_configs == 21        # padded to 24 rows over 4 shards
    want_p90 = np.asarray(parent_p90(prof, grid, seg, len(cfgs)))
    for k in one.metric_names:
        np.testing.assert_array_equal(four[k], one[k], err_msg=k)
    np.testing.assert_array_equal(np.float32(one["latency_p90_ms"]),
                                  want_p90, err_msg="p90 fold")
    per_row = _sweep_summaries(prof, wl, de, None, None, None, grid,
                               n_requests=N_REQ, warmup=WARMUP,
                               mesh=None)
    folded = UA.aggregate_block_summaries(per_row, seg, len(cfgs))
    folded["latency_p90_ms"] = want_p90
    for k, v in folded.items():
        np.testing.assert_array_equal(np.float32(one[k]),
                                      np.asarray(v).reshape(one[k].shape),
                                      err_msg=f"fold: {k}")
    # the merged histogram has one row per config, not per block row
    lead = (prof.n_fleets,) if prof.is_stacked else ()
    for m in (None, mesh):
        out = _sweep_summaries(prof, wl, de, None, None, None, grid,
                               n_requests=N_REQ, warmup=WARMUP, mesh=m,
                               segments=seg)
        assert out["latency_hist"].shape == lead + (3, UA.HIST_BINS)
        assert out["latency_ms"].shape == lead + (21,)
print("OK")
"""


def test_histogram_merge_on_shards_bitwise_in_forced_4_device_subprocess():
    """The per-config histogram merge runs inside the device program: on
    4 forced CPU devices, with configs of 7, 5 and 9 blocks straddling
    shard boundaries and a grid padded from 21 to 24 rows, the sharded
    run equals the single-device run and a fold written here (per-row
    histogram, segment sum, percentile: the merge done after the run)
    bit for bit over every metric, for one fleet and a stacked pair;
    ``_sweep_summaries`` returns the histogram with one row per config."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", _SUBPROC_MERGE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_k1_sweep_bit_identical_to_unblocked_engine():
    """user_block >= max n_users is a no-op for EVERY metric across a
    mixed sweep, workloads and dispatch engines included."""
    sw = Sweep(policy=("MO", "RR"), n_users=(5, 10), seed=(0, 1))
    for wl in (None, bundled_trace()):
        ref = run(Scenario(n_requests=150, workload=wl), sw)
        out = run(Scenario(n_requests=150, workload=wl, user_block=16),
                  sw)
        for k in ref.metric_names:
            np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


# ----------------------------------- K > 1 semantics and aggregation ----

def test_multi_block_equals_manual_per_block_runs():
    """A K-block config's metrics are exactly the left-fold combination
    of its blocks run one at a time: means fold as float32 sum/count,
    throughput sums (parallel replicas), makespan maxes."""
    prof = paper_fleet()
    cfg = SimConfig(n_users=20, n_requests=120, seed=5)
    grid, seg = _make_user_grid(prof, [cfg], 8)
    assert grid.n_configs == 3            # 8 + 8 + 4 users
    wl, de = MarkovWorkload(), StaticDispatch()
    warmup = 12

    # one segment per row: the merge leaves each block's own histogram
    per_block = _sweep_summaries(prof, wl, de, None, None, None, grid,
                                 n_requests=120, warmup=warmup,
                                 mesh=None, segments=np.arange(3))
    hists = per_block.pop("latency_hist")
    assert hists.shape == (3, UA.HIST_BINS)
    # each block row == its own single-row run (the engine's vmap
    # invariant, extended to block rows)
    for b in range(3):
        row = ConfigGrid(*[leaf[b:b + 1] for leaf in grid])
        solo = _sweep_summaries(prof, wl, de, None, None, None, row,
                                n_requests=120, warmup=warmup, mesh=None)
        for k in per_block:
            _assert_metric_equal(k, per_block[k][b], solo[k][0],
                                 err_msg=f"block {b}: {k}")

    res = run(Scenario(n_users=20, n_requests=120, seed=5, user_block=8))
    for k, v in per_block.items():
        blocks = np.asarray(v, np.float32)
        if k == "throughput_rps":
            want = np.float32(0.0)
            for x in blocks:
                want = np.float32(want + x)
        elif k == "makespan_s":
            want = blocks.max()
        elif k == "latency_p90_ms":
            # exact fleet-wide percentile: the merged per-block histogram
            merged = np.asarray(hists, np.float32).sum(0)
            want = np.float32(1000.0 * UA.histogram_p90(merged))
        else:
            acc = np.float32(0.0)
            for x in blocks:
                acc = np.float32(acc + x)
            want = np.float32(acc / np.float32(3.0))
        np.testing.assert_array_equal(
            np.float32(res.scalar(k)), want, err_msg=k)


def test_hist_p90_partition_invariant_and_matches_dense():
    """The merged-histogram p90 is a pure function of the pooled sample:
    any K-way split of the same latencies gives a bit-identical merged
    histogram, hence a bit-identical percentile (0 ULP — stronger than
    the 1-ULP pin the contract asks for); the estimator itself tracks
    ``np.percentile`` within the log-bin quantization (~0.55%
    relative)."""
    rng = np.random.default_rng(0)
    lat = rng.lognormal(-2.0, 1.0, size=4096).astype(np.float32)
    dense = np.asarray(UA.latency_histogram(lat))
    assert dense.shape == (UA.HIST_BINS,)
    assert dense.sum() == lat.size
    for k in (2, 3, 7, 16):
        merged = np.zeros_like(dense)
        for part in np.array_split(lat, k):
            merged = merged + np.asarray(UA.latency_histogram(part))
        np.testing.assert_array_equal(merged, dense, err_msg=f"K={k}")
        np.testing.assert_array_equal(
            np.asarray(UA.histogram_p90(merged)),
            np.asarray(UA.histogram_p90(dense)), err_msg=f"K={k}")
    est = float(UA.histogram_p90(dense))
    ref = float(np.percentile(np.asarray(lat, np.float64), 90))
    assert abs(est - ref) / ref < 5e-3, (est, ref)


def test_user_block_is_a_static_sweep_axis():
    """user_block sweeps like any STATIC_AXES field — one fused program
    per value — and the K=1 column equals the un-blocked run."""
    assert "user_block" in STATIC_AXES
    sw = Sweep(user_block=(4, 16), seed=(0, 1))
    res = run(Scenario(n_users=12, n_requests=100), sw)
    assert res["latency_ms"].shape == (2, 2)
    ref = run(Scenario(n_users=12, n_requests=100), Sweep(seed=(0, 1)))
    np.testing.assert_array_equal(
        res.sel("latency_ms", user_block=16), ref["latency_ms"])
    # the 3-block column is a different physical system, not a reshuffle
    assert not np.array_equal(res.sel("latency_ms", user_block=4),
                              ref["latency_ms"])


def test_records_rejects_multi_block_configs():
    with pytest.raises(ValueError, match="user_block"):
        records(Scenario(n_users=50, user_block=8))
    with pytest.raises(ValueError, match="user_block"):
        records(Scenario(n_users=4, user_block=8),
                Sweep(n_users=(4, 50)))
    recs = records(Scenario(n_users=4, n_requests=50, user_block=8))
    assert recs["latency"].shape == (50,)


# ------------------------------------------- scenario spec plumbing -----

def test_user_block_spec_roundtrip_and_hash_stability():
    """user_block enters the spec/hash only when set: every pre-user-axis
    scenario keeps its exact hash (the committed bench baseline depends
    on it), and blocked scenarios round-trip through JSON."""
    base = Scenario()
    assert "user_block" not in base.to_json()
    assert base.hash == Scenario(user_block=None).hash

    sc = Scenario(user_block=512)
    assert sc.to_json()["user_block"] == 512
    assert sc.hash != base.hash
    rt = Scenario.from_json(sc.to_json())
    assert rt == sc and rt.user_block == 512

    with pytest.raises(ValueError, match="user_block"):
        Scenario(user_block=0)
    with pytest.raises(ValueError, match="user_block"):
        Scenario(user_block=-3)


def test_gateway_adopts_scenario_stream_count():
    """A scenario-built gateway sizes its estimator state to the
    scenario's fleet: n_users streams by default, never shrinking below
    the constructor default, explicit n_streams= still winning."""
    from repro.serving.gateway import WindowedGateway

    prof = paper_fleet()
    assert WindowedGateway(prof).n_streams == 1024
    assert WindowedGateway(Scenario(n_users=15)).n_streams == 1024
    gw = WindowedGateway(Scenario(n_users=5000))
    assert gw.n_streams == 5000
    assert gw._counts.shape == (5000,)
    assert WindowedGateway(Scenario(n_users=5000),
                           n_streams=8192).n_streams == 8192


# ----------------------------------------- acceptance: 10^5 / 10^6 ------

def test_run_completes_at_1e5_users_and_beats_looped_path_10x():
    """Acceptance (ISSUE 7): one run() at n_users=10^5 completes on CPU
    CI, and its users/sec is >= 10x the looped per-value path (the dense
    user axis: one program per n_users value). The dense side is timed
    at a smaller width and extrapolated LINEARLY to 10^5 users at equal
    total requests — dense per-step cost grows at least linearly in U
    (argmin + per-user scatters), so the extrapolation flatters the
    dense baseline and the bar is conservative. Both sides are measured
    back-to-back per attempt (same pairing as the grid-build test) so
    host load hits numerator and denominator together."""
    N, C, R = 100_000, 1024, 32
    K = UA.n_user_blocks(N, C)
    sc = Scenario(n_users=N, n_requests=R, user_block=C,
                  warmup_frac=0.25)
    res = run(sc)                              # compile + complete
    for k in res.metric_names:
        assert np.isfinite(res.scalar(k)), k
    assert res.scalar("throughput_rps") > 0

    DENSE_U = 8192
    dsc = Scenario(n_users=DENSE_U, n_requests=R, warmup_frac=0.25)
    run(dsc)                                   # compile the dense side

    attempts = []
    for _ in range(3):
        t0 = time.perf_counter()
        run(dsc)
        t_dense_small = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(sc)
        t_blocked = time.perf_counter() - t0
        # dense at 10^5 users, equal total requests (K*R steps, one
        # program): steps scale by K, per-step cost by >= N/DENSE_U
        t_dense = t_dense_small * K * (N / DENSE_U)
        users_blocked = N / t_blocked
        users_dense = N / t_dense
        attempts.append((users_blocked, users_dense))
        if users_blocked >= 10 * users_dense:
            break
    assert any(b >= 10 * d for b, d in attempts), attempts


@pytest.mark.skipif("REPRO_MILLION_USERS" not in os.environ,
                    reason="10^6-user acceptance run is opt-in "
                           "(REPRO_MILLION_USERS=1): ~10^3 block rows, "
                           "minutes of CPU")
def test_run_completes_at_1e6_users():
    sc = Scenario(n_users=1_000_000, n_requests=8, user_block=1024,
                  warmup_frac=0.25)
    res = run(sc)
    assert np.isfinite(res.scalar("latency_ms"))
    assert res.scalar("throughput_rps") > 0
