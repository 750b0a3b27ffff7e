"""The edge-to-cloud offloading tier's pinning suite.

Three layers of protection around ``repro.core.cloud``:

  * golden regression — every ``cloud=None`` scenario stays bit-identical
    to ``tests/golden_cloud_pr7.json`` (the pre-CloudTier engine's values;
    see ``scripts/capture_golden_engine.py``), on a single device AND a
    forced 4-device mesh;
  * properties — a zero-cost cloud pair (rtt=0, bw=inf, xfer-energy=0)
    scores bitwise like a local pair with the same profile; offload share
    is monotone non-increasing in RTT; CloudTier round-trips through
    JSON; specs/hashes without a cloud are untouched by the feature;
  * integration — the serving gateway adopts a scenario's cloud, the
    pods= hierarchical router gets its auto-appended cloud pod, and the
    no-cloud gateway keeps the fused kernel path.
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

from repro.core.cloud import (CloudTier, default_cloud_pairs,
                              default_payload_kb)
from repro.core.policies import mo_scores
from repro.core.profiles import ProfileTable, paper_fleet, synthetic_fleet
from repro.core.scenario import Scenario, Sweep, records, run

REPO = Path(__file__).resolve().parent.parent
GOLDEN = REPO / "tests" / "golden_cloud_pr7.json"

f32 = jnp.float32


def _golden():
    return json.loads(GOLDEN.read_text())


# ------------------------------------------------- golden regression --

def test_records_bit_identical_to_pr7_golden():
    """Every record scenario captured pre-CloudTier replays bit-for-bit
    through the offload-aware engine with cloud=None, and its spec is
    still canonical (same JSON in == same JSON out, hence same hash)."""
    for entry in _golden()["records"]:
        sc = Scenario.from_json(entry["scenario"])
        assert sc.to_json() == entry["scenario"]
        recs = records(sc)
        for k, want in entry["records"].items():
            np.testing.assert_array_equal(
                np.asarray(recs[k], np.float64), np.asarray(want),
                err_msg=f"{entry['scenario']}:{k}")


def test_sweep_bit_identical_to_pr7_golden():
    fix = _golden()["sweep"]
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
from repro.core.cloud import CloudTier
from repro.core.scenario import Scenario, Sweep, run
from repro.launch.mesh import make_sweep_mesh

assert len(jax.devices()) == 4, jax.devices()
mesh = make_sweep_mesh()

# cloud=None sharded across 4 real devices still reproduces the PR 7
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

# cloud-ACTIVE sweeps shard bitwise too: same CloudMeta replicated to
# every device, sharded == single for each metric including the share.
csc = Scenario(n_requests=150, cloud=CloudTier())
csw = Sweep(policy=("MO", "LT"), n_users=(3, 7), seed=(0,))
ref = run(csc, csw)
out = run(csc, csw, mesh=mesh)
for k in ref.metric_names:
    if k == "latency_p90_ms":
        np.testing.assert_allclose(out[k], ref[k], rtol=3e-7, err_msg=k)
    else:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)
assert "offload_share" in ref.metric_names
print("OK")
"""


def test_cloud_golden_in_forced_4_device_subprocess():
    """PR 7 golden + cloud-active sharding on a real 4-device mesh
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


# ------------------------------------------------ offload properties --

@st.composite
def zero_cost_case(draw):
    P = draw(st.integers(2, 10))
    G = draw(st.integers(2, 6))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    prof = ProfileTable(jnp.asarray(rng.uniform(10, 500, (P, G))),
                        jnp.asarray(rng.uniform(0.01, 0.5, (P, G))),
                        jnp.asarray(rng.uniform(1, 99, (P, G))))
    i = draw(st.integers(0, P - 1))          # local pair the cloud mirrors
    g = draw(st.integers(0, G - 1))
    q = rng.integers(0, 10, P + 1).astype(np.float32)
    q[P] = q[i]                              # same queue depth both sides
    gamma = draw(st.floats(0.0, 1.0))
    delta = draw(st.floats(0.0, 60.0))
    return prof, i, g, jnp.asarray(q), gamma, delta


@settings(max_examples=40, deadline=None)
@given(zero_cost_case())
def test_zero_cost_cloud_pair_scores_bitwise_like_local(case):
    """rtt=0, bw=inf, xfer-energy=0: the extension is free, so a cloud
    pair mirroring a local pair's profile gets the SAME bits out of
    Algorithm 1 — extension rows, congestion penalty (identically zero)
    and scores included. Offload-vs-local is then pure profile economics,
    which is the design invariant the tier rests on."""
    prof, i, g, q, gamma, delta = case
    mirror = ProfileTable(prof.T[i:i + 1], prof.E[i:i + 1],
                          prof.mAP[i:i + 1], ("cloud/mirror",))
    tier = CloudTier(rtt_ms=0.0, bw_mbps=float("inf"),
                     xfer_energy_mj_per_kb=0.0, cloud_pairs=mirror)
    ext, meta = tier.extend(prof)
    P = prof.n_pairs
    np.testing.assert_array_equal(np.asarray(ext.T[P]), np.asarray(prof.T[i]))
    np.testing.assert_array_equal(np.asarray(ext.E[P]), np.asarray(prof.E[i]))
    pen = meta.penalty(g, q)
    np.testing.assert_array_equal(np.asarray(pen), 0.0)
    J, _ = mo_scores(ext.T[:, g], ext.E[:, g], ext.mAP[:, g], q,
                     delta=delta, gamma=gamma, penalty=pen)
    Jn = np.asarray(J)
    assert Jn[P].tobytes() == Jn[i].tobytes()


def test_offload_share_monotone_non_increasing_in_rtt():
    """Raising the round-trip time can only make offloading less
    attractive: the MO policy's offload share never increases with RTT,
    and a far-away cloud (1 s RTT) is mostly ignored."""
    rtts = (0.0, 40.0, 200.0, 1000.0)
    res = run(Scenario(n_users=7, n_requests=200, seed=0),
              Sweep(cloud=[CloudTier(rtt_ms=r) for r in rtts]))
    share = np.asarray(res["offload_share"], np.float64).ravel()
    assert share.shape == (4,)
    assert share[0] > 0.3                  # a free-ish cloud gets used
    assert np.all(np.diff(share) <= 1e-6)  # monotone non-increasing
    assert share[-1] < share[0]


def test_records_offload_routes_to_extended_pairs():
    sc = Scenario(n_users=6, n_requests=150, seed=1, cloud=CloudTier())
    recs = records(sc)
    srv = np.asarray(recs["server"], np.int64)
    P = paper_fleet().n_pairs
    assert srv.max() >= P            # some requests actually offloaded
    assert srv.max() < P + default_cloud_pairs().n_pairs
    # the same scenario minus the cloud never leaves the local fleet
    srv0 = np.asarray(records(replace(sc, cloud=None))["server"])
    assert srv0.max() < P


# --------------------------------------------------- JSON round-trip --

def test_cloud_tier_json_roundtrip():
    # defaults serialize to the minimal spec (shared hash rule)
    t = CloudTier()
    spec = t.to_json()
    assert set(spec) == {"rtt_ms", "bw_mbps", "xfer_energy_mj_per_kb"}
    assert CloudTier.from_json(json.loads(json.dumps(spec))) == t
    # custom pairs + payload + infinite bandwidth survive the string form
    pairs = synthetic_fleet(jax.random.PRNGKey(0), 5)
    t2 = CloudTier(rtt_ms=12.5, bw_mbps=float("inf"),
                   xfer_energy_mj_per_kb=0.0,
                   cloud_pairs=ProfileTable(pairs.T[:2], pairs.E[:2],
                                            pairs.mAP[:2],
                                            ("cloud/a", "cloud/b")),
                   payload_kb=np.linspace(30, 90, 5))
    back = CloudTier.from_json(json.loads(json.dumps(t2.to_json())))
    assert back == t2 and back.bw_mbps == float("inf")
    np.testing.assert_array_equal(back.payload_kb, t2.payload_kb)
    np.testing.assert_array_equal(np.asarray(back.cloud_pairs.T),
                                  np.asarray(t2.cloud_pairs.T))
    assert CloudTier.from_json(None) is None


def test_cloud_tier_validation():
    with pytest.raises(ValueError):
        CloudTier(rtt_ms=-1.0)
    with pytest.raises(ValueError):
        CloudTier(bw_mbps=0.0)
    with pytest.raises(ValueError):
        CloudTier(xfer_energy_mj_per_kb=-0.1)
    with pytest.raises(ValueError):
        CloudTier(payload_kb=np.array([-1.0, 2.0]))
    with pytest.raises(ValueError):
        default_cloud_pairs(n_groups=3)
    with pytest.raises(ValueError):
        CloudTier(payload_kb=np.ones(3)).extend(paper_fleet())


def test_scenario_cloud_spec_and_hash():
    """No-cloud specs are untouched by the feature: no "cloud" key, same
    hash as before; a cloud scenario round-trips by value with a
    discriminating hash."""
    assert "cloud" not in Scenario().to_json()
    assert Scenario(cloud=None).hash == Scenario().hash
    sc = Scenario(n_users=5, cloud=CloudTier(rtt_ms=80.0))
    back = Scenario.from_json(json.dumps(sc.to_json()))
    assert back == sc and back.hash == sc.hash
    assert back.to_json() == sc.to_json()
    assert back.cloud == CloudTier(rtt_ms=80.0)
    assert sc.hash != Scenario(n_users=5).hash
    assert Scenario(cloud=CloudTier(rtt_ms=10.0)).hash \
        != Scenario(cloud=CloudTier(rtt_ms=20.0)).hash


def test_cloud_rejects_stacked_profiles():
    profs = [synthetic_fleet(jax.random.PRNGKey(k), 5) for k in (0, 1)]
    with pytest.raises(ValueError, match="stacked"):
        run(Scenario(n_requests=60, cloud=CloudTier()),
            Sweep(profile=profs))


def test_mixed_cloud_axis_fills_offload_share():
    """A sweep mixing cloud=None with real tiers still reports one
    rectangular offload_share array: the no-cloud slices are zero."""
    res = run(Scenario(n_users=5, n_requests=120, seed=0),
              Sweep(cloud=[None, CloudTier(rtt_ms=40.0)]))
    share = np.asarray(res["offload_share"], np.float64).ravel()
    assert share.shape == (2,)
    assert share[0] == 0.0 and share[1] > 0.0


# ------------------------------------------------ serving integration --

def test_gateway_adopts_scenario_cloud_and_pods():
    from repro.serving.gateway import WindowedGateway

    sc = Scenario(n_users=8, n_requests=120, cloud=CloudTier(rtt_ms=0.0))
    gw = WindowedGateway(sc)
    P = paper_fleet().n_pairs
    assert gw.prof.n_pairs == P + default_cloud_pairs().n_pairs
    pairs, _, _ = gw.route_window(np.arange(16), np.zeros(gw.prof.n_pairs))
    assert int(np.max(np.asarray(pairs))) >= P    # cheap cloud gets picked

    # pods: a local-only pod vector gets the cloud pod appended
    gw2 = WindowedGateway(sc, pods=[0, 0, 1, 1, 2])
    assert np.asarray(gw2._pod_of_pair).tolist() == [0, 0, 1, 1, 2, 3, 3]
    p2, _, _ = gw2.route_window(np.arange(8), np.zeros(gw2.prof.n_pairs))
    assert p2.shape == (8,)

    with pytest.raises(ValueError, match="MO"):
        WindowedGateway(paper_fleet(), policy="LC", pods=[0, 0, 1, 1, 2])


def test_no_cloud_gateway_keeps_fused_path():
    from repro.serving.gateway import WindowedGateway

    gw = WindowedGateway(paper_fleet())
    assert gw._cloud_meta is None and gw._pod_of_pair is None
    pairs, _, _ = gw.route_window(np.arange(4), np.zeros(5))
    assert int(np.max(np.asarray(pairs))) < 5


def test_serving_plane_offloads_with_cloud_scenario():
    from repro.serving.engine import ServingPlane

    sc = Scenario(n_users=10, n_requests=200, cloud=CloudTier(), seed=0)
    plane = ServingPlane.build(sc, window=32)
    recs = plane.run(192)
    served = np.asarray(recs["pair"], np.int64)
    P = paper_fleet().n_pairs
    assert served.max() >= P
    summ = ServingPlane.summarize(recs)
    assert summ["latency_ms"] > 0


def test_default_payload_scales_with_group():
    pl = default_payload_kb(5)
    assert pl.shape == (5,) and np.all(np.diff(pl) > 0)
    # xfer time: KB -> kbit over Mbps, zero at infinite bandwidth
    t = CloudTier(bw_mbps=16.0)
    np.testing.assert_allclose(t.xfer_ms(5), pl * 8.0 / 16.0, rtol=1e-6)
    assert np.all(CloudTier(bw_mbps=float("inf")).xfer_ms(5) == 0.0)
