"""Chip smoke test: drive the serving plane and the what-if sweep engine
once on a TPU, through the entry points a user calls, and check what
comes out.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # sharded sweeps only, four chips

One chip: the paper's 5-pair fleet served by ``ServingPlane`` under static
and online dispatch, a 1024-pair city-scale fleet with 10^5 streams, the
Fig. 4 policy sweep and a 10^5-user sweep through ``core.scenario.run``.
Every routed window's decisions from the compiled ``pallas_hoisted``
kernel are compared with the ``xla`` backend on the same chip, and one
window with a NumPy fp32 replay of Algorithm 1. With ``--chips 4`` the
script runs only the sharded sweeps (``mesh="local"``) and compares them
bit for bit with the same grids on one device.

The script needs a TPU and exits non-zero without one; it never falls
back to the CPU. All data comes from seeds. Timings printed on earlier
lines are chip readings. The last line of stdout is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

POLICIES = ("MO", "RR", "RND", "LC", "LE", "LT", "HA")
FIG4_USERS = (1, 3, 5, 7, 9, 11, 13, 15)
FIG4_SEEDS = (0, 1, 2)


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------ the references --
#
# Both references are teacher-forced on the kernel's own decisions: request
# i is scored at the queue depths that the kernel's first i decisions left,
# so one disagreement does not carry into the rest of the window. A
# disagreement passes only where the reference scores the kernel's choice
# and its own exactly the same (an exact tie, which a last-bit difference
# in the chip's arithmetic may break either way); ties are counted and
# printed.

def numpy_scores(T_g, E_g, mAP_g, q, *, delta: float, gamma: float):
    """Algorithm 1's scores for one request in NumPy float32: the plain
    reference for ``core.policies.mo_scores``."""
    f4 = np.float32
    big = f4(1e30)
    feasible = mAP_g >= mAP_g.max() - f4(delta)
    L = T_g * (f4(1.0) + q)
    l_min = np.where(feasible, L, big).min()
    l_max = np.where(feasible, L, -big).max()
    e_min = np.where(feasible, E_g, big).min()
    e_max = np.where(feasible, E_g, -big).max()
    Ln = (L - l_min) / np.maximum(l_max - l_min, f4(1e-9))
    En = (E_g - e_min) / np.maximum(e_max - e_min, f4(1e-9))
    return np.where(feasible, f4(gamma) * Ln + f4(1.0 - gamma) * En, big)


def check_numpy(tbl, gs, q0, pairs, *, delta: float, gamma: float) -> int:
    """Replay one routed window request by request in NumPy float32.
    Returns the number of exact ties; raises on any other disagreement."""
    T, E, M = (np.asarray(a, np.float32) for a in (tbl.T, tbl.E, tbl.mAP))
    q = np.asarray(q0, np.float32).copy()
    ties = 0
    for i, (g, p) in enumerate(zip(np.asarray(gs), np.asarray(pairs))):
        J = numpy_scores(T[:, g], E[:, g], M[:, g], q, delta=delta,
                         gamma=gamma)
        c = int(np.argmin(J))
        if c != p:
            if J[c] != J[p]:
                raise AssertionError(
                    f"request {i}: kernel chose pair {p} (score {J[p]!r}), "
                    f"the NumPy replay pair {c} (score {J[c]!r})")
            ties += 1
        q[p] += np.float32(1.0)
    return ties


@functools.partial(jax.jit, static_argnames=("delta", "gamma"))
def _xla_forced(T, E, mAP, gs, q0, pairs, *, delta: float, gamma: float):
    """The ``xla`` backend's per-request step (``mo_scores`` + argmin),
    teacher-forced on ``pairs``: its choice at every request, and whether
    it scores that choice and the forced one exactly the same."""
    from repro.core.policies import mo_scores

    def step(q, gp):
        g, p = gp
        J, _ = mo_scores(T[:, g], E[:, g], mAP[:, g], q, delta=delta,
                         gamma=gamma)
        c = jnp.argmin(J).astype(jnp.int32)
        return q.at[p].add(1.0), (c, J[c] == J[p])

    _, (choice, tie) = jax.lax.scan(step, q0, (gs, pairs))
    return choice, tie


def check_xla(tbl, gs, q0, pairs, q_after, *, delta, gamma) -> int:
    """Compare one routed window with the ``xla`` backend on the same
    chip. Returns the number of exact ties; raises on any other
    disagreement."""
    from repro.kernels.moscore import moscore_route

    pairs = np.asarray(pairs)
    want_q = q0 + np.bincount(pairs, minlength=tbl.T.shape[0])
    if not np.array_equal(np.asarray(q_after), want_q.astype(np.float32)):
        raise AssertionError("kernel queue feedback != its own decisions")
    ref, _ = moscore_route(tbl.T, tbl.E, tbl.mAP, gs, q0, delta=delta,
                           gamma=gamma, backend="xla")
    if np.array_equal(np.asarray(ref), pairs):
        return 0
    choice, tie = _xla_forced(tbl.T, tbl.E, tbl.mAP, gs, jnp.asarray(q0),
                              jnp.asarray(pairs), delta=delta, gamma=gamma)
    bad = np.asarray(choice) != pairs
    wrong = np.flatnonzero(bad & ~np.asarray(tie))
    if wrong.size:
        i = int(wrong[0])
        raise AssertionError(
            f"request {i}: kernel chose pair {pairs[i]}, xla chose "
            f"{int(np.asarray(choice)[i])}, and their scores differ "
            f"({wrong.size} such requests)")
    return int(bad.sum())


# ------------------------------------------------------ served phases --

def record_windows(gw) -> list:
    """Keep each routed window's inputs and outputs. Device arrays are
    immutable, so holding the pre-route dispatch state costs nothing."""
    log, route = [], gw.route_window

    def recorded(stream_ids, queue_depths):
        state = gw._dstate
        out = route(stream_ids, queue_depths)
        log.append((state, np.asarray(queue_depths, np.float32), out))
        return out

    gw.route_window = recorded
    return log


def assert_kernel_path(gw, window: int) -> None:
    """The gateway routes MO windows through the compiled Mosaic kernel:
    ``auto`` resolved to ``pallas_hoisted`` and the routing program holds
    the TPU custom call (interpret mode would hold none)."""
    if gw.backend != "pallas_hoisted":
        raise AssertionError(f"gateway resolved backend {gw.backend!r}")
    if gw._cloud_meta is not None or gw._fault_meta is not None \
            or gw._pod_of_pair is not None or gw.policy != "MO":
        raise AssertionError("gateway does not take the fused MO path")
    ids = jnp.arange(window, dtype=jnp.int32) % gw.n_streams
    q0 = jnp.zeros((gw.prof.n_pairs,), jnp.float32)
    hlo = gw._route_fused.lower(gw._dstate, gw._counts, q0,
                                ids).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("routing program holds no Mosaic kernel")


def serve(label: str, scenario, *, window: int, n_windows: int):
    """Serve ``n_windows`` windows (the first one compiles), check every
    window's decisions against the ``xla`` backend and the first warm
    one against the NumPy replay, and return the gateway."""
    from repro.serving import ServingPlane

    plane = ServingPlane.build(scenario, window=window)
    gw = plane.gateway
    log = record_windows(gw)
    t0 = time.perf_counter()
    recs = plane.run(n_requests=window * n_windows)
    wall = time.perf_counter() - t0
    if len(log) != n_windows:
        raise AssertionError(f"{label}: routed {len(log)} windows")
    summary = ServingPlane.summarize(recs)
    for k, v in summary.items():
        if not np.isfinite(v):
            raise AssertionError(f"{label}: {k} = {v}")
    win_s = np.asarray(recs["router_window_s"])
    warm = win_s[1:]
    say(f"chip reading: serve {label} " + json.dumps({
        "backend": gw.backend, "pairs": gw.prof.n_pairs,
        "streams": plane.n_streams, "window": window,
        "windows": n_windows,
        "compile_and_first_window_s": float(win_s[0]),
        "warm_router_p50_ms": float(np.percentile(warm, 50) * 1e3),
        "warm_router_p99_ms": float(np.percentile(warm, 99) * 1e3),
        "warm_routed_req_per_s": float(window * len(warm) / warm.sum()),
        "plane_wall_s": wall, "latency_ms": summary["latency_ms"],
        "energy_mwh": summary["energy_mwh"], "map": summary["map"]}))

    t0 = time.perf_counter()
    delta, gamma = float(gw.delta), float(gw.gamma)
    xla_ties = 0
    for state, q0, (pairs, gs, q) in log:
        tbl = gw.dispatch.tables(state, gw.prof)
        xla_ties += check_xla(tbl, gs, q0, pairs, q, delta=delta,
                              gamma=gamma)
    state, q0, (pairs, gs, _q) = log[1]
    np_ties = check_numpy(gw.dispatch.tables(state, gw.prof), gs, q0, pairs,
                          delta=delta, gamma=gamma)
    say(f"chip reading: decisions {label} " + json.dumps({
        "xla_compared_windows": len(log),
        "xla_compared_requests": sum(len(o[2][0]) for o in log),
        "xla_exact_ties": xla_ties, "numpy_compared_requests": len(gs),
        "numpy_exact_ties": np_ties,
        "check_s": time.perf_counter() - t0}))
    return gw


# ------------------------------------------------------- sweep phases --

def baseline_rows(suite: str) -> dict:
    rows = json.loads((ROOT / "benchmarks" / "bench_baseline.json")
                      .read_text())["suites"][suite]["rows"]
    return {r.split(",")[0]: r.split(",")[2] for r in rows}


def fig4(n_requests: int = 1500, users=FIG4_USERS, seeds=FIG4_SEEDS):
    from repro.core.scenario import Scenario, Sweep

    return Scenario(n_requests=n_requests), Sweep(
        policy=POLICIES, n_users=users, seed=seeds)


def users_1e5(n_users: int = 100_000, user_block: int = 1024):
    from repro.core.scenario import Scenario

    return Scenario(n_users=n_users, user_block=user_block), None


def timed_run(scenario, sweep):
    from repro.core.scenario import run

    t0 = time.perf_counter()
    res = run(scenario, sweep)
    return res, time.perf_counter() - t0


def assert_finite(label: str, res) -> None:
    for k in res.metric_names:
        if not np.all(np.isfinite(res[k])):
            raise AssertionError(f"{label}: non-finite {k}")


def sweep_fig4(grid=None) -> dict:
    """The Fig. 4 grid: the paper's orderings must hold."""
    scenario, sweep = grid or fig4()
    res, cold = timed_run(scenario, sweep)
    assert_finite("fig4", res)
    users = list(dict(sweep.axes)["n_users"])
    mean = {m: res.mean(m, over="seed") for m in res.metric_names}
    j, mo, ha = users.index(max(users)), POLICIES.index("MO"), \
        POLICIES.index("HA")
    lat, en, mp = mean["latency_ms"], mean["energy_mwh"], mean["map"]
    head = {
        "fig4.headline_mo_vs_ha_latency": float(lat[mo, j] / lat[ha, j]),
        "fig4.headline_mo_vs_ha_energy": float(en[mo, j] / en[ha, j]),
        "fig4.headline_map_gap_pct":
            float(100 * (mp[ha, j] - mp[mo, j]) / mp[ha, j]),
    }
    if not head["fig4.headline_mo_vs_ha_latency"] < 1.0:
        raise AssertionError(f"MO latency not below HA's: {head}")
    if not head["fig4.headline_mo_vs_ha_energy"] < 1.0:
        raise AssertionError(f"MO energy not below HA's: {head}")
    if not abs(head["fig4.headline_map_gap_pct"]) <= 10.0:
        raise AssertionError(f"MO-vs-HA mAP gap beyond 10%: {head}")
    cpu = baseline_rows("fig4")
    for k, v in head.items():
        say(f"chip reading: {k} users={max(users)} {v!r} "
            f"(CPU baseline row, jax 0.4.37 --fast: {cpu[k]})")
    n_cfg = int(np.prod(sweep.shape))
    say(f"chip reading: sweep fig4 configs={n_cfg} "
        f"n_requests={scenario.n_requests} compile_and_run_s={cold!r}")
    return head


def sweep_users(grid=None) -> dict:
    """The 10^5-user path: one fused program of user-block rows."""
    scenario, sweep = grid or users_1e5()
    res, cold = timed_run(scenario, sweep)
    assert_finite("users", res)
    _, warm = timed_run(scenario, sweep)
    out = {"latency_ms": res.scalar("latency_ms"),
           "map": res.scalar("map"), "compile_and_run_s": cold,
           "warm_s": warm, "warm_users_per_s": scenario.n_users / warm}
    say(f"chip reading: sweep users n_users={scenario.n_users} "
        f"user_block={scenario.user_block} {json.dumps(out)}")
    return out


def sharded_equals_single(label: str, grid) -> None:
    """The same grid on one device and sharded over every local device
    (``mesh="local"``), in this process: bit for bit."""
    scenario, sweep = grid
    one, t_one = timed_run(scenario, sweep)
    assert_finite(label, one)
    four, t_four = timed_run(replace(scenario, mesh="local"), sweep)
    for k in one.metric_names:
        np.testing.assert_array_equal(np.asarray(four[k]),
                                      np.asarray(one[k]), err_msg=k)
    say(f"chip reading: sharded==single {label} bitwise over "
        f"{len(one.metric_names)} metrics; single_s={t_one!r} "
        f"sharded_s={t_four!r} (both include compilation)")


# --------------------------------------------------------------- main --

def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweeps on four chips")
    args = ap.parse_args()
    cache = enable_compile_cache()

    from repro.kernels.moscore import BACKEND_ENV

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX found {len(devices)} devices")
    if os.environ.get(BACKEND_ENV):
        fail(f"{BACKEND_ENV} is set; the smoke checks the platform's "
             f"own backend choice")
    dev = devices[0]
    say(f"chip reading: platform={dev.platform} "
        f"device_kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")

    if args.chips == 4:
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh()
        if sorted(d.id for d in mesh.devices.flat) \
                != sorted(d.id for d in devices):
            fail(f"sweep mesh {mesh} does not span all {len(devices)} chips")
        sharded_equals_single("fig4", fig4())
        from repro.core.scenario import Sweep
        sharded_equals_single(
            "users_1e5", (users_1e5()[0], Sweep(policy=("MO", "HA"))))
    else:
        import jax.random as jr

        from repro.core.dispatch import OnlineDispatch
        from repro.core.profiles import synthetic_fleet
        from repro.core.scenario import Scenario

        paper = Scenario(policy="MO", n_users=1024)
        city = Scenario(profile=synthetic_fleet(jr.PRNGKey(0), 1024),
                        policy="MO", n_users=100_000)
        for label, scenario, window, n_windows in (
                ("paper_static", paper, 1024, 17),
                ("paper_online", replace(paper, dispatch=OnlineDispatch()),
                 1024, 17),
                ("city_static", city, 4096, 9)):
            gw = serve(label, scenario, window=window, n_windows=n_windows)
            assert_kernel_path(gw, window)
        sweep_fig4()
        sweep_users()

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
