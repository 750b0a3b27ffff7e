"""Multi-device sweep sharding: bit-identical results across meshes, and
the padding helper's invariants. The in-process tests run on whatever
devices exist (a 1-device mesh still exercises the shard_map path); the
true multi-device guarantee is checked in a subprocess with 4 forced host
devices, so it holds even on single-device CI runners."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.profiles import paper_fleet, stack_profiles, synthetic_fleet
from repro.core.scenario import Scenario, Sweep, run
from repro.core.simulator import ConfigGrid, SimConfig, _make_grid
from repro.distributed.sharding import config_axis_spec, pad_leading
from repro.launch.mesh import make_sweep_mesh

REPO = Path(__file__).resolve().parent.parent


def _small_sweep(mesh=None, prof=None):
    return run(Scenario(profile=prof if prof is not None else "paper",
                        n_requests=250, mesh=mesh),
               Sweep(policy=("MO", "LT", "HA"), n_users=(3, 7),
                     seed=(0, 1)))


def test_sharded_equals_single_on_local_mesh():
    """shard_map path == plain vmap path, bit for bit (any device count;
    12 configs over the mesh exercises padding whenever the device count
    doesn't divide 12)."""
    ref = _small_sweep()
    out = _small_sweep(mesh="local")
    for k in ref.metric_names:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


def test_sharded_equals_single_stacked_fleet():
    fleets = stack_profiles(
        [synthetic_fleet(jax.random.PRNGKey(i), 5) for i in range(2)])
    ref = _small_sweep(prof=fleets)
    out = _small_sweep(mesh="local", prof=fleets)
    assert ref.axes[0] == "fleet" and ref["latency_ms"].shape[0] == 2
    for k in ref.metric_names:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


def test_sharded_equals_single_trace_workload():
    """The trace workload shards like the Markov one: its device-resident
    trace is replicated and the config axis split, bit-identically."""
    from repro.data.traces import bundled_trace

    sc = Scenario(workload=bundled_trace(), n_requests=200)
    sw = Sweep(policy=("MO", "LT"), n_users=(3, 7), seed=(0, 1))
    ref = run(sc, sw)
    out = run(sc, sw, mesh=make_sweep_mesh())
    for k in ref.metric_names:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


_SUBPROC_CHECK = """
import json
import jax, numpy as np
from repro.core.scenario import Scenario, Sweep, run
from repro.data.traces import bundled_trace
from repro.launch.mesh import make_sweep_mesh

assert len(jax.devices()) == 4, jax.devices()
sw = Sweep(policy=("MO", "RR", "LC", "LT", "HA"), n_users=(3, 7),
           seed=(0,))                         # 10 configs -> padded to 12
sc = Scenario(n_requests=150)
ref = run(sc, sw)
mesh = make_sweep_mesh()
out = run(sc, sw, mesh=mesh)
for k in ref.metric_names:
    np.testing.assert_array_equal(out[k], ref[k], err_msg=k)

# Markov regression vs the PR 2 golden fixture, on a real 4-device mesh:
# neither the WorkloadSource refactor nor the Scenario layer may move a
# single bit even sharded.
fix = json.load(open({golden!r}))["sweep"]
gold = run(Scenario(n_requests=fix["n_requests"], mesh="local"),
           Sweep(policy=tuple(fix["policies"]),
                 n_users=tuple(fix["user_levels"]),
                 seed=tuple(fix["seeds"])))
for k, v in fix["metrics"].items():
    want = np.asarray(v).reshape(gold[k].shape)
    np.testing.assert_array_equal(gold[k], want, err_msg=k)

# Trace workload: sharded == single on 4 real devices too.
tsc = Scenario(workload=bundled_trace(), n_requests=150)
tsw = Sweep(policy=("MO", "LT"), n_users=(3, 7), seed=(0,))
t_ref = run(tsc, tsw)
t_out = run(tsc, tsw, mesh=mesh)
for k in t_ref.metric_names:
    np.testing.assert_array_equal(t_out[k], t_ref[k], err_msg=k)
print("OK")
"""


def test_sharded_bitwise_in_forced_4_device_subprocess():
    """Real multi-device bit-exactness, via xla_force_host_platform_device
    _count=4 in a fresh process (the flag only takes effect at jax init):
    sharded == single for both workload sources, and the Markov path still
    reproduces the PR 2 golden metrics bit for bit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(REPO / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    src = _SUBPROC_CHECK.format(
        golden=str(REPO / "tests" / "golden_markov_pr2.json"))
    res = subprocess.run([sys.executable, "-c", src], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_pad_leading_pads_and_preserves():
    prof = paper_fleet()
    cfgs = [SimConfig(n_users=u, n_requests=100, seed=u) for u in (2, 5, 9)]
    grid = _make_grid(prof, cfgs)
    padded, n = pad_leading(grid, 4)
    assert n == 3
    assert all(leaf.shape[0] == 4 for leaf in jax.tree.leaves(padded))
    for name in ConfigGrid._fields:
        a, b = np.asarray(getattr(padded, name)), \
            np.asarray(getattr(grid, name))
        np.testing.assert_array_equal(a[:3], b, err_msg=name)
        np.testing.assert_array_equal(a[3], b[0], err_msg=name)
    same, n = pad_leading(grid, 3)
    assert n == 3 and same is grid


def test_config_axis_spec_uses_every_mesh_axis():
    mesh = make_sweep_mesh()
    spec = config_axis_spec(mesh)
    # a 1-axis tuple normalises to its bare name in a PartitionSpec
    names = mesh.axis_names
    assert tuple(spec) == ((names if len(names) > 1 else names[0]),)
    ragged = ConfigGrid(*(jnp.zeros((3,)),) * 6,
                        jnp.zeros((2, 2)), jnp.zeros((3, 4)),
                        jnp.zeros((3, 4)))
    with pytest.raises(ValueError, match="leading dim"):
        pad_leading(ragged, 4)
