"""Closed-loop discrete-event simulator of the heterogeneous serving fleet
(paper §IV: Locust-style concurrency — each of U users has exactly one
request in flight; the next request of a stream is issued when the previous
response returns).

NOTE — public API: scenarios are declared through ``repro.core.scenario``
(``Scenario`` / ``Sweep`` / ``run`` / ``records``); the kwarg entry
points here (``make_grid`` / ``simulate`` / ``simulate_batch`` /
``sweep_grid`` / ``run_policy`` / ``sweep``) are deprecation-warned thin
shims over that path, kept bit-identical to the pre-scenario engine
(``tests/golden_static_pr3.json`` pins it). This module remains the
*engine*: the traced core, the batched/sharded execution paths and the
summarizers all live here and are driven by the scenario layer.

Implemented as one ``lax.scan`` over dispatch events whose per-config
parameters (policy code, γ, Δ, stickiness, RNG state) are *traced*
arguments, so an entire Fig. 4-style grid — policy × concurrency × γ ×
seed — runs as ONE ``jax.vmap``-ped scan inside ONE jit
(:func:`simulate_batch` / :func:`sweep_grid`): a single device program
instead of one trace + launch per configuration. Differing concurrency
levels share the trace by padding users to ``n_users_max`` and masking the
padded streams to ``t = +inf`` so they never dispatch.

Scene complexity comes from a pluggable :class:`~repro.core.workload.
WorkloadSource` (the ``workload=`` argument throughout): the source owns
the initial per-user count draw at grid-build time and the per-dispatch
count step inside the scan. The default is the paper's synthetic Markov
chain (``repro.core.workload.MarkovWorkload``, bit-identical to the
engine before the interface existed); ``repro.data.traces.TraceWorkload``
plays recorded object-count traces instead. Sources are pytrees
replicated across the config axis, so both compose with vmap, sharding
and fleet stacking unchanged.

Dispatch state is pluggable the same way (``dispatch=`` throughout,
``repro.core.dispatch``): the per-decision state — round-robin counter,
online-EWMA belief tables — lives in a ``DispatchState`` pytree carried
through the scan, with ``init``/``select``/``observe`` hooks shared with
the serving gateway. ``StaticDispatch`` (default) is bit-identical to
the pre-interface engine; ``OnlineDispatch`` adapts to observations, and
a ``DriftSchedule`` (``drift=``) perturbs the *true* profile mid-run to
model throttling or model swaps.

Bit-exactness across batching: jax's threefry draws are not prefix-stable
across shapes (the first U samples of a ``(U_max,)`` draw differ from a
``(U,)`` draw), so the initial per-user complexity states are drawn
per-config at grid-build time (:func:`make_grid`) with each config's own
``n_users`` shape and passed into the scan as data. Every other draw in
the loop is shape-independent, which makes a padded batched run reproduce
each config's unpadded trajectory exactly.

Scaling axes (see ``docs/sweep_engine.md`` for the full architecture
guide): the batched engine composes three orthogonal batch dims in the
fixed order **(fleet, config, user, time)** —

  * **config** — the flat struct-of-arrays axis of :class:`ConfigGrid`
    (one entry per policy × users × γ × Δ × oracle × seed combination),
    vmapped always and optionally *sharded across devices* via
    ``sweep_grid(..., mesh=...)`` (``shard_map`` over the config axis,
    padded to a multiple of the device count, bit-identical results);
  * **fleet** — an optional leading ensemble axis over same-shape
    ``ProfileTable`` stacks (``repro.core.profiles.stack_profiles``),
    vmapped outside the config axis;
  * **user / time** — the per-config padded user streams and the
    ``lax.scan`` over dispatch events.

Grid building is memoized and vectorised: per-config initial draws depend
only on (seed, stickiness, n_users), so :func:`make_grid` computes each
distinct triple once per workload source (process-wide for the Markov
default, see ``repro.core.workload.grid_cache_info``) and batches cache
misses per ``n_users`` level with one vmapped threefry draw — a
10^5-config grid builds in milliseconds.

Faithfulness notes:
  * service time / energy / accuracy are drawn from ``ProfileTable`` at the
    *true* complexity group; the policy only sees the *estimated* group
    (output-based estimator, paper §III-B.1), so estimator staleness and
    accuracy-dependent undercounting are modelled;
  * queue depths q[p] are exact (outstanding requests at dispatch time);
  * reported energy = per-request profile energy + the amortised active-floor
    power of the fleet (reproduces Fig. 4e/5d's decreasing energy curves).
"""

from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.common import spans
from repro.core import estimator as EST
from repro.core.dispatch import (DispatchEngine, DriftSchedule,
                                 default_dispatch)
from repro.core.policies import POLICY_CODES
from repro.core.profiles import ProfileTable
from repro.core.useraxis import (aggregate_block_summaries, block_segments,
                                 block_sizes, latency_histogram,
                                 segment_user_sum)
from repro.core.workload import (MarkovWorkload, WorkloadSource,
                                 _init_draws, default_workload,
                                 grid_cache_clear, grid_cache_info)
from repro.distributed.sharding import config_axis_spec, pad_leading

# Historical home of the grid draw machinery — tests and callers import
# these from here; the implementations moved to repro.core.workload with
# the WorkloadSource split.
__all__ = ["SimConfig", "ConfigGrid", "make_grid", "simulate",
           "simulate_batch", "summarize", "summarize_batch", "run_policy",
           "sweep", "sweep_grid", "SWEEP_AXES", "grid_cache_info",
           "grid_cache_clear", "_init_draws", "default_workload",
           "default_dispatch"]

f32 = jnp.float32
i32 = jnp.int32


@dataclass(frozen=True)
class SimConfig:
    n_users: int = 15
    n_requests: int = 2000
    policy: str = "MO"
    gamma: float = 0.5
    delta: float = 20.0   # headline tolerance (paper leaves Δ_mAP to the
                          # operator; 20 pts reproduces the Fig.4 trade-off)
    stickiness: float = 0.85
    seed: int = 0
    warmup_frac: float = 0.1
    oracle_estimator: bool = False   # ablation: g_est = g_true (perfect
                                     # complexity knowledge; benchmarks)
    workload: WorkloadSource | None = field(default=None, compare=False)
    # scene-complexity source; None = the Markov default. All configs in
    # one grid must share a single source (it is grid data, like prof).
    dispatch: DispatchEngine | None = field(default=None, compare=False)
    # dispatch-state engine; None = StaticDispatch. Like the workload, it
    # is grid data: every config in one grid must share a single engine.


class ConfigGrid(NamedTuple):
    """Struct-of-arrays batch of simulator configs — the traced leaves of a
    ``SimConfig``. All fields have leading dim (B,); ``rng`` is the (B, 2)
    uint32 scan key and ``true0`` the (B, n_users_max) initial true object
    counts, both drawn host-side per config (see module docstring);
    ``phase`` is the (B, n_users_max) per-user frame phase offset of the
    workload source (zeros for the Markov chain). ``simulate`` also uses
    it batch-less (scalar leaves, (U,) true0/phase) so single and vmapped
    paths share one by-name field access path."""

    policy_code: jax.Array      # (B,) int32 index into POLICY_CODES
    n_users: jax.Array          # (B,) int32 live concurrency (<= n_users_max)
    gamma: jax.Array            # (B,) float32
    delta: jax.Array            # (B,) float32
    stickiness: jax.Array       # (B,) float32
    oracle: jax.Array           # (B,) bool   g_est = g_true ablation
    rng: jax.Array              # (B, 2) uint32
    true0: jax.Array            # (B, n_users_max) int32
    phase: jax.Array            # (B, n_users_max) int32 workload phase

    @property
    def n_configs(self) -> int:
        return int(self.policy_code.shape[0]) if self.policy_code.ndim \
            else 1

    @property
    def n_users_max(self) -> int:
        return int(self.true0.shape[-1])


def _resolve_workload(workload, cfgs=()) -> WorkloadSource:
    """One workload source for a whole grid: the explicit argument wins;
    otherwise the single source the configs agree on (None = Markov
    default). Mixing sources in one grid is an error — the source is grid
    data shared by every config, exactly like the profile table."""
    found = {id(c.workload): c.workload for c in cfgs
             if c.workload is not None}
    if workload is None and found:
        if len(found) > 1:
            raise ValueError("configs in one grid must share a single "
                             "workload source")
        (workload,) = found.values()
    elif workload is not None and any(w is not workload
                                      for w in found.values()):
        raise ValueError("workload= argument conflicts with the configs' "
                         "own workload source")
    return workload if workload is not None else default_workload()


def _resolve_dispatch(dispatch, cfgs=()) -> DispatchEngine:
    """One dispatch engine for a whole grid, mirroring
    :func:`_resolve_workload`: the explicit argument wins, otherwise the
    single engine the configs agree on (None = :class:`StaticDispatch`).
    Mixing engines in one grid is an error — the engine is grid data
    shared by every config, exactly like the profile table. Unlike
    workload sources (identity-keyed: a trace's equality IS identity),
    engines are frozen hyper-parameter dataclasses, so two separately
    constructed but equal engines count as the same one."""
    found: list[DispatchEngine] = []
    for c in cfgs:
        if c.dispatch is not None and c.dispatch not in found:
            found.append(c.dispatch)
    if dispatch is None and found:
        if len(found) > 1:
            raise ValueError("configs in one grid must share a single "
                             "dispatch engine")
        (dispatch,) = found
    elif dispatch is not None and any(d != dispatch for d in found):
        raise ValueError("dispatch= argument conflicts with the configs' "
                         "own dispatch engine")
    return dispatch if dispatch is not None else default_dispatch()


def _warn_legacy(name: str, alt: str) -> None:
    """Issue the deprecation warning for a legacy kwarg entry point.
    The category lives in repro.core.scenario (imported lazily — the
    scenario module imports this one); ``stacklevel=3`` points the
    warning at the shim's caller."""
    from repro.core.scenario import LegacyAPIWarning
    warnings.warn(
        f"repro.core.simulator.{name} is deprecated: {alt} — see the "
        "migration table in docs/sweep_engine.md",
        LegacyAPIWarning, stacklevel=3)


def make_grid(prof: ProfileTable, configs,
              n_users_max: int | None = None,
              workload: WorkloadSource | None = None,
              dispatch: DispatchEngine | None = None) -> ConfigGrid:
    """Deprecated: declare the grid as a ``Scenario`` + ``Sweep`` and
    call ``repro.core.scenario.run`` / ``records`` instead (the engine
    builds the grid internally). Same contract as :func:`_make_grid`."""
    _warn_legacy("make_grid", "use repro.core.scenario.run(Scenario, "
                 "Sweep) — grids are built internally")
    return _make_grid(prof, configs, n_users_max, workload, dispatch)


def _make_grid(prof: ProfileTable, configs,
               n_users_max: int | None = None,
               workload: WorkloadSource | None = None,
               dispatch: DispatchEngine | None = None) -> ConfigGrid:
    """Pack an iterable of :class:`SimConfig` into a padded
    :class:`ConfigGrid`.

    Args:
      prof: the fleet the grid will run against; only its ``n_groups``
        enters the build (the initial complexity draw). A stacked table is
        fine — all fleets share one group count.
      configs: iterable of :class:`SimConfig`. All must agree on
        ``n_requests``/``warmup_frac``: those are scan-*shape* parameters,
        not traced grid leaves, and are passed separately to
        :func:`simulate_batch` / :func:`summarize_batch`.
      n_users_max: pad width of the user axis; defaults to the largest
        ``n_users`` in the batch. Padded streams are masked to never
        dispatch, so the pad width does not change results.
      workload: scene-complexity source drawing the initial states (and
        later stepped inside the scan — pass the SAME source to
        ``simulate_batch``). Defaults to the configs' shared source, else
        the Markov chain.
      dispatch: dispatch-state engine the grid will run under
        (``repro.core.dispatch``). It holds no grid-build data — the
        argument is validated here (one engine per grid, like the
        workload) and must be passed again to ``simulate_batch``.

    Returns:
      A :class:`ConfigGrid` with leading dim ``B = len(configs)``
      (struct-of-arrays; see the class docstring for leaf shapes/dtypes).

    Determinism: each config's initial state is drawn with its own
    ``n_users``-shaped threefry stream keyed on (seed, stickiness), so row
    ``b`` of any batched/sharded run is bit-identical to the unbatched
    ``simulate`` of config ``b``. Markov draws are memoized process-wide
    on (seed, stickiness, n_users, n_groups) and cache misses are computed
    in one vmapped batch per ``n_users`` level (see
    ``repro.core.workload.grid_cache_info``).
    """
    cfgs = list(configs)
    if not cfgs:
        raise ValueError("empty config grid")
    if len({(c.n_requests, c.warmup_frac) for c in cfgs}) > 1:
        raise ValueError(
            "configs in one grid must agree on n_requests/warmup_frac "
            "(they are scan-shape parameters, passed separately to "
            "simulate_batch/summarize_batch)")
    workload = _resolve_workload(workload, cfgs)
    _resolve_dispatch(dispatch, cfgs)
    U = max(c.n_users for c in cfgs) if n_users_max is None else n_users_max
    G = prof.n_groups

    keys = [(c.seed, float(c.stickiness), c.n_users, G) for c in cfgs]
    draws = workload.grid_draws(keys)

    true0 = np.zeros((len(cfgs), U), np.int32)
    rng = np.zeros((len(cfgs), 2), np.uint32)
    phase = np.zeros((len(cfgs), U), np.int32)
    for i, k in enumerate(keys):
        t0, r, ph = draws[k]
        true0[i, :k[2]] = t0
        rng[i] = r
        phase[i, :k[2]] = ph
    return ConfigGrid(
        policy_code=jnp.asarray([POLICY_CODES[c.policy] for c in cfgs], i32),
        n_users=jnp.asarray([c.n_users for c in cfgs], i32),
        gamma=jnp.asarray([c.gamma for c in cfgs], f32),
        delta=jnp.asarray([c.delta for c in cfgs], f32),
        stickiness=jnp.asarray([c.stickiness for c in cfgs], f32),
        oracle=jnp.asarray([c.oracle_estimator for c in cfgs], bool),
        rng=jnp.asarray(rng),
        true0=jnp.asarray(true0),
        phase=jnp.asarray(phase),
    )


def _expand_user_blocks(cfgs, user_block: int):
    """Decompose each config into its user blocks (balancer replicas, see
    ``repro.core.useraxis``): returns ``(rows, segments)`` where ``rows``
    is a flat list of ``(cfg_index, block_index, block_users)`` — one
    entry per expanded grid row, configs' blocks contiguous — and
    ``segments`` maps each row back to its config (int32)."""
    rows: list[tuple[int, int, int]] = []
    blocks_per_cfg = []
    for ci, c in enumerate(cfgs):
        sizes = block_sizes(c.n_users, user_block)
        blocks_per_cfg.append(len(sizes))
        rows.extend((ci, bi, bu) for bi, bu in enumerate(sizes))
    return rows, block_segments(blocks_per_cfg)


def _make_user_grid(prof: ProfileTable, configs, user_block: int,
                    workload: WorkloadSource | None = None,
                    dispatch: DispatchEngine | None = None,
                    chunk: int | None = None):
    """Pack configs into a user-blocked :class:`ConfigGrid`: a config
    with ``n_users = N > user_block`` becomes ``ceil(N / user_block)``
    block rows of ≤ ``user_block`` users each — independent balancer
    replicas riding the ordinary config axis, so the grid vmaps, shards
    over a mesh and fleet-stacks with zero new engine machinery, and its
    leaves stay ``O(total_users)`` instead of ``O(B × n_users_max)``.

    Returns ``(grid, segments)``; feed both to
    :func:`_sweep_user_summaries` to recover per-config metrics by
    segment reduction over each config's contiguous block rows.

    Determinism contract:
      * single-block configs (``n_users <= user_block``) draw through the
        legacy memoized one-shot path (:meth:`WorkloadSource.grid_draws`)
        and aggregate as one-element folds, so they stay bit-identical
        to the un-blocked engine (the golden fixtures pin this);
      * multi-block configs draw through the streamed per-user-keyed path
        (:meth:`WorkloadSource.stream_draws`, device memory bounded by
        ``chunk``) and block ``b`` scans under ``fold_in(rng0, b)`` — a
        distinct physical system (K replicas, not one balancer), declared
        as such by ``user_block`` entering the scenario identity/hash.

    ``n_requests`` stays the PER-BLOCK scan length (it is a static scan
    shape): a K-block config serves ``K × n_requests`` requests total.
    """
    cfgs = list(configs)
    if not cfgs:
        raise ValueError("empty config grid")
    if len({(c.n_requests, c.warmup_frac) for c in cfgs}) > 1:
        raise ValueError(
            "configs in one grid must agree on n_requests/warmup_frac "
            "(they are scan-shape parameters, passed separately to "
            "simulate_batch/summarize_batch)")
    workload = _resolve_workload(workload, cfgs)
    _resolve_dispatch(dispatch, cfgs)
    G = prof.n_groups
    rows, segments = _expand_user_blocks(cfgs, user_block)
    U = max(bu for _, _, bu in rows)
    B = len(rows)

    multi = {ci for ci, bi, _ in rows if bi > 0}
    if multi:
        workload.validate_user_block(user_block)
    legacy_keys = {ci: (c.seed, float(c.stickiness), c.n_users, G)
                   for ci, c in enumerate(cfgs) if ci not in multi}
    draws = workload.grid_draws(list(legacy_keys.values())) \
        if legacy_keys else {}
    streams: dict[tuple, tuple] = {}
    for ci in sorted(multi):
        c = cfgs[ci]
        sk = (c.seed, float(c.stickiness), c.n_users)
        if sk not in streams:
            streams[sk] = workload.stream_draws(
                c.seed, c.stickiness, n_groups=G, n_users=c.n_users,
                chunk=chunk)

    true0 = np.zeros((B, U), np.int32)
    rng = np.zeros((B, 2), np.uint32)
    phase = np.zeros((B, U), np.int32)
    fold_rows: list[int] = []
    fold_keys: list[np.ndarray] = []
    for i, (ci, bi, bu) in enumerate(rows):
        c = cfgs[ci]
        if ci in multi:
            t0, r0, ph = streams[(c.seed, float(c.stickiness), c.n_users)]
            lo = bi * user_block
            true0[i, :bu] = t0[lo:lo + bu]
            phase[i, :bu] = ph[lo:lo + bu]
            fold_rows.append(i)
            fold_keys.append(r0)
        else:
            t0, r0, ph = draws[legacy_keys[ci]]
            true0[i, :bu] = t0
            phase[i, :bu] = ph
            rng[i] = r0
    if fold_rows:
        # per-block scan keys: fold the block index into the config's
        # stream key, one vmapped threefry program for all multi rows
        folded = np.asarray(jax.vmap(jax.random.fold_in)(
            jnp.asarray(np.stack(fold_keys), jnp.uint32),
            jnp.asarray([rows[i][1] for i in fold_rows], i32)))
        rng[fold_rows] = folded

    grid = ConfigGrid(
        policy_code=jnp.asarray([POLICY_CODES[cfgs[ci].policy]
                                 for ci, _, _ in rows], i32),
        n_users=jnp.asarray([bu for _, _, bu in rows], i32),
        gamma=jnp.asarray([cfgs[ci].gamma for ci, _, _ in rows], f32),
        delta=jnp.asarray([cfgs[ci].delta for ci, _, _ in rows], f32),
        stickiness=jnp.asarray([cfgs[ci].stickiness
                                for ci, _, _ in rows], f32),
        oracle=jnp.asarray([cfgs[ci].oracle_estimator
                            for ci, _, _ in rows], bool),
        rng=jnp.asarray(rng),
        true0=jnp.asarray(true0),
        phase=jnp.asarray(phase),
    )
    return grid, segments


def _sweep_user_summaries(prof, workload, dispatch, drift, cloud, faults,
                          grid: ConfigGrid, segments, n_cfgs: int, *,
                          n_requests: int, warmup: int, mesh: Mesh | None):
    """Fused sweep over a user-blocked grid: the expanded block rows run
    through the ordinary single-device/sharded paths (per-user workload
    state rides the sharded config axis), then segment-reduce back to
    per-config metrics on device. Single-block configs pass through the
    aggregation bit-identically; multi-block configs additionally carry
    the latency histogram, merged per config inside the device program,
    so the fleet-wide p90 is an exact merge, not a mean of per-block
    percentiles."""
    multi = int(np.asarray(segments).shape[0]) > n_cfgs
    out = _sweep_summaries(prof, workload, dispatch, drift, cloud, faults,
                           grid, n_requests=n_requests, warmup=warmup,
                           mesh=mesh, segments=segments if multi else None)
    return aggregate_block_summaries(out, segments, n_cfgs, block_axis=-1)


def _simulate_core(prof: ProfileTable, workload: WorkloadSource,
                   dispatch: DispatchEngine, drift: DriftSchedule | None,
                   cloud, faults, policy_code, n_users, gamma, delta,
                   oracle, stickiness, rng, true0, phase, *,
                   n_requests: int):
    """Trace body shared by the single and batched paths. Every config
    parameter is a traced array; the only static shapes are ``n_requests``
    (scan length), ``true0``'s length (``n_users_max``) and the workload /
    dispatch / drift pytrees' own data. Padded users (index >= n_users)
    sit at ``t_next = +inf`` and never dispatch.

    The dispatch engine's :class:`~repro.core.dispatch.DispatchState`
    rides in the scan carry: ``select`` scores each request against the
    engine's belief tables, ``observe`` folds the request's TRUE service
    time and energy back in afterwards. ``drift`` (when given) perturbs
    the *true* profile per step — the policy never sees it except through
    observations.

    ``cloud`` (:class:`~repro.core.cloud.CloudMeta` or ``None``) marks
    the trailing pairs of ``prof`` as remote: their profiled latency
    already includes RTT + transfer, so the truth model splits it back
    into uplink occupancy (a single shared uplink serialises transfers —
    the ``up_avail`` carry key, present only when a cloud tier exists),
    remote compute (occupies the cloud pair) and downlink RTT (occupies
    neither). The dispatcher additionally sees a congestion penalty
    (:meth:`CloudMeta.penalty`) on latency-aware policies. ``None``
    leaves the traced graph exactly as before — the no-cloud fixtures
    stay bit-identical.

    ``faults`` (:class:`~repro.core.faults.FaultMeta` or ``None``) is
    the fault plane: per-step outage/throttle/jitter draws keyed purely
    on the step index (no carried fault state). A visible schedule
    passes the health mask to dispatch (down pairs leave the candidate
    set, with MO's degraded argmin-latency fallback); the TRUTH model
    always applies faults — dispatching into an outage stalls the
    request by ``timeout_ms``, throttling scales the drifted truth
    (drift first, fault throttle on top — the defined composition
    order), and WAN jitter perturbs the cloud transfer/RTT terms.
    Fault-active records additionally carry ``slo_violation`` (no
    healthy pair cleared the accuracy bar at dispatch) and ``failed``
    (the request hit a down pair). ``None`` leaves the traced graph
    exactly as before — the no-fault fixtures stay bit-identical."""
    P = prof.n_pairs
    G = prof.n_groups
    U = true0.shape[0]
    code = jnp.asarray(policy_code, i32)
    wctx = workload.prepare(G, stickiness)
    mask = jnp.arange(U) < n_users

    carry = {
        "t_next": jnp.where(mask, jnp.arange(U, dtype=f32) * 1e-4, jnp.inf),
        "true_cnt": true0.astype(i32),
        "est_cnt": true0.astype(i32),
        "pos": jnp.zeros((U,), i32),     # dispatches so far per user
        "server_by_user": jnp.full((U,), -1, i32),
        "finish_by_user": jnp.zeros((U,), f32),
        "avail": jnp.zeros((P,), f32),
        "dispatch": dispatch.init(prof),
        "rng": rng,
    }
    if cloud is not None:
        carry["up_avail"] = jnp.asarray(0.0, f32)   # shared uplink frontier

    gamma = jnp.asarray(gamma, f32)
    delta = jnp.asarray(delta, f32)
    oracle = jnp.asarray(oracle, bool)
    phase = jnp.asarray(phase, i32)

    def step(c, i):
        u = jnp.argmin(c["t_next"])
        t = c["t_next"][u]
        rng, k1, k2, k3 = jax.random.split(c["rng"], 4)

        new_true = workload.next_count(wctx, k1, c["true_cnt"][u], u,
                                       phase[u] + c["pos"][u] + 1)
        g_true = EST.group_of_count(new_true, G)
        g_est = jnp.where(oracle, g_true,
                          EST.group_of_count(c["est_cnt"][u], G))

        active = (c["finish_by_user"] > t) & (c["server_by_user"] >= 0)
        q = jnp.zeros((P,), f32).at[c["server_by_user"]].add(
            active.astype(f32), mode="drop")

        if faults is not None:
            down = faults.down_at(i)
            up = ~down
            health = jnp.where(jnp.any(up), up, True)

        penalty = None if cloud is None else cloud.penalty(g_est, q)
        p, dstate = dispatch.select(
            c["dispatch"], prof, code, g_est, q, k2, gamma, delta,
            penalty=penalty,
            health=health if faults is not None and faults.visible
            else None)

        # the TRUE fleet this step: the offline profile, or its drifted
        # copy — service time, energy and the observation all come from
        # it. Fault throttling multiplies ON TOP of drift (the defined
        # composition order: truth = (prof x drift) x fault).
        truth = prof if drift is None else drift.at_step(prof, i)
        if faults is not None and faults.has_throttle:
            t_sc, e_sc = faults.throttle_at(i)
            truth = ProfileTable(truth.T * t_sc[:, None],
                                 truth.E * e_sc[:, None],
                                 truth.mAP, truth.names, truth.floor_mw)
        t_serv = truth.T[p, g_true] / 1000.0                  # ms -> s
        # dispatching into an outage stalls the request by timeout_ms —
        # the truth model pays it whether or not the router could see
        # the mask (blind routing is the static-routing baseline)
        stall = None
        if faults is not None and faults.has_down:
            stall = jnp.where(down[p], faults.timeout_ms, 0.0) / 1000.0
        if cloud is None:
            start = jnp.maximum(t, c["avail"][p])
            finish = start + t_serv
            if stall is not None:
                finish = finish + stall
        else:
            # split the profiled total back into uplink / compute / RTT:
            # the uplink is a single shared resource (transfers serialise),
            # remote compute occupies the cloud pair, the downlink RTT
            # occupies neither. Local pairs have zero network terms, so
            # their timeline is the exact no-cloud expression. WAN jitter
            # perturbs the REALIZED transfer/RTT; the compute split keeps
            # the profiled base terms (the remote GPU is not jittered).
            isc = cloud.is_cloud[p]
            xfer_s = jnp.where(isc, cloud.xfer_ms[g_true], 0.0) / 1000.0
            rtt_s = jnp.where(isc, cloud.rtt_ms, 0.0) / 1000.0
            xfer_j, rtt_j = xfer_s, rtt_s
            if faults is not None and faults.has_bw_jitter:
                xfer_j = xfer_s * faults.xfer_scale(i)
            if faults is not None and faults.has_rtt_jitter:
                rtt_j = rtt_s + jnp.where(
                    isc, faults.rtt_extra_ms(i), 0.0) / 1000.0
            up_start = jnp.maximum(t, c["up_avail"])
            arrive = jnp.where(isc, up_start + xfer_j, t)
            start = jnp.maximum(arrive, c["avail"][p])
            compute_s = jnp.maximum(t_serv - xfer_s - rtt_s, 0.0)
            finish = start + compute_s + rtt_j
            if stall is not None:
                finish = finish + stall
            nc_up = jnp.where(isc, up_start + xfer_j, c["up_avail"])

        detected = EST.noisy_detected_count(k3, new_true, prof.mAP[p, g_true])
        dstate = dispatch.observe(dstate, p, g_est, truth.T[p, g_true],
                                  truth.E[p, g_true])

        nc = dict(c)
        nc["rng"] = rng
        nc["true_cnt"] = c["true_cnt"].at[u].set(new_true.astype(i32))
        nc["est_cnt"] = c["est_cnt"].at[u].set(detected)
        nc["pos"] = c["pos"].at[u].add(1)
        nc["server_by_user"] = c["server_by_user"].at[u].set(p)
        nc["finish_by_user"] = c["finish_by_user"].at[u].set(finish)
        if cloud is None:
            nc["avail"] = c["avail"].at[p].set(finish)
        else:
            nc["avail"] = c["avail"].at[p].set(finish - rtt_j)
            nc["up_avail"] = nc_up
        nc["t_next"] = c["t_next"].at[u].set(finish)
        nc["dispatch"] = dstate

        rec = {
            "t_arrival": t,
            "latency": finish - t,
            "energy": truth.E[p, g_true],
            "map": prof.mAP[p, g_true],
            "server": p,
            "g_true": g_true,
            "g_est": g_est,
            "q_at_dispatch": q[p],
            "correct_group": (g_true == g_est).astype(f32),
        }
        if faults is not None:
            # SLO violation = the degraded-mode condition: no UP pair
            # clears the accuracy bar (belief mAP == offline mAP — it is
            # never adapted or drifted); failed = dispatched into an
            # outage (always true-model ``down``, not the relaxed mask)
            feas = prof.mAP[:, g_est] >= jnp.max(prof.mAP[:, g_est]) - delta
            rec["slo_violation"] = (~jnp.any(feas & up)).astype(f32)
            rec["failed"] = down[p].astype(f32)
        return nc, rec

    _, recs = jax.lax.scan(step, carry, jnp.arange(n_requests, dtype=i32))
    return recs


def _simulate_config(prof, workload, dispatch, drift, cloud, faults,
                     g: ConfigGrid, *, n_requests: int):
    """One config (scalar ConfigGrid leaves) -> record arrays; fields are
    accessed by name so batched and single paths can't transpose leaves."""
    return _simulate_core(prof, workload, dispatch, drift, cloud, faults,
                          g.policy_code, g.n_users, g.gamma, g.delta,
                          g.oracle, g.stickiness, g.rng, g.true0, g.phase,
                          n_requests=n_requests)


@functools.partial(jax.jit, static_argnames=("n_requests",))
def _simulate_one(prof, workload, dispatch, drift, cloud, faults,
                  g: ConfigGrid, *, n_requests: int):
    return _simulate_config(prof, workload, dispatch, drift, cloud, faults,
                            g, n_requests=n_requests)


def _over_fleet(fn, prof):
    """Apply ``fn(single_fleet_prof)``, vmapping over the leading fleet
    axis when ``prof`` is stacked. The fleet axis always batches OUTSIDE
    the config axis — axis order (fleet, config, user, time)."""
    if prof.is_stacked:
        return jax.vmap(fn)(prof)
    return fn(prof)


@functools.partial(jax.jit, static_argnames=("n_requests",))
def _simulate_vmapped(prof, workload, dispatch, drift, cloud, faults,
                      grid: ConfigGrid, *, n_requests: int):
    return _over_fleet(
        lambda pf: jax.vmap(
            lambda g: _simulate_config(pf, workload, dispatch, drift,
                                       cloud, faults, g,
                                       n_requests=n_requests))(
            grid),
        prof)


def _fused_summaries(prof, workload, dispatch, drift, cloud, faults,
                     grid: ConfigGrid, segments=None, *, n_requests: int,
                     warmup: int, num_configs: int = 0):
    """The simulate + summarize composition over (fleet,) config — the ONE
    source of truth shared by the single-device jit and the shard_map'ed
    path, so the two can never drift apart and break the bit-identical
    guarantee. Returns (B,) metric vectors — (F, B) for a stacked fleet —
    without materialising (B, N) records.

    ``segments`` ((B,) int32, one config id per grid row) additionally
    emits the ``latency_hist`` leaf, ``(num_configs, NB)`` — ``(F,
    num_configs, NB)`` for a stacked fleet: each row's fixed-bin latency
    histogram, segment-summed into its config's. Rows with id
    ``num_configs`` (padding) are dropped. The counts are integer-valued
    float32, so the merge is exact in any grouping up to 2^24 per bin."""
    with_hist = segments is not None

    def per_fleet(pf):
        def one(g):
            recs = _simulate_config(pf, workload, dispatch, drift, cloud,
                                    faults, g, n_requests=n_requests)
            return _summarize_core(recs, pf, warmup, cloud,
                                   with_hist=with_hist)

        out = jax.vmap(one)(grid)
        if with_hist:
            out["latency_hist"] = segment_user_sum(
                out["latency_hist"], segments, num_configs)
        return out

    return _over_fleet(per_fleet, prof)


@functools.partial(jax.jit,
                   static_argnames=("n_requests", "warmup", "num_configs"))
def _sweep_fused(prof, workload, dispatch, drift, cloud, faults,
                 grid: ConfigGrid, segments=None, *, n_requests: int,
                 warmup: int, num_configs: int = 0):
    return _fused_summaries(prof, workload, dispatch, drift, cloud, faults,
                            grid, segments, n_requests=n_requests,
                            warmup=warmup, num_configs=num_configs)


@functools.lru_cache(maxsize=None)
def _sweep_sharded_fn(mesh: Mesh, n_requests: int, warmup: int,
                      stacked: bool, num_configs: int = 0):
    """Build (and cache per mesh/shape signature) the shard_map'ed fused
    sweep: the config axis is split over every mesh axis, the profile
    table, workload source, dispatch engine, drift schedule and cloud
    meta are replicated, and each shard runs the plain vmapped simulate +
    summarize. The metric leaves stay config-sharded. With
    ``num_configs`` set the program also takes the rows' config ids
    (sharded like the grid): each shard merges the latency histograms of
    the rows it holds into per-config partials, and one ``psum`` over
    the mesh adds them (integer counts, exact), so the ``(C, NB)``
    histogram comes out replicated and no per-row histogram leaves a
    shard. The inner jit re-specialises per workload/dispatch/drift/
    cloud pytree structure, so one cache entry serves Markov and trace
    runs, static and online engines, edge-only and edge+cloud fleets."""
    cspec = config_axis_spec(mesh)
    out_spec = PartitionSpec(None, *cspec) if stacked else cspec
    with_hist = num_configs > 0

    fused = functools.partial(_fused_summaries, n_requests=n_requests,
                              warmup=warmup, num_configs=num_configs)

    def inner(*args):
        out = fused(*args)
        if with_hist:
            out["latency_hist"] = jax.lax.psum(out["latency_hist"],
                                               mesh.axis_names)
        return out

    def fn(pf, wl, de, dr, cl, fl, g, seg):
        keys = jax.eval_shape(fused, pf, wl, de, dr, cl, fl, g, seg).keys()
        specs = {k: PartitionSpec() if k == "latency_hist" else out_spec
                 for k in keys}
        # check_vma=False: the policy lax.switch mixes branches that
        # read config-sharded state with branches that read only
        # replicated inputs, whose output types the varying-axes check
        # refuses to unify
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(PartitionSpec(), PartitionSpec(), PartitionSpec(),
                      PartitionSpec(), PartitionSpec(), PartitionSpec(),
                      cspec, cspec if with_hist else PartitionSpec()),
            out_specs=specs, check_vma=False)(pf, wl, de, dr, cl, fl, g,
                                              seg)

    return jax.jit(fn)


def _sweep_summaries(prof, workload, dispatch, drift, cloud, faults,
                     grid: ConfigGrid, *, n_requests: int, warmup: int,
                     mesh: Mesh | None, segments=None):
    """Dispatch a fused sweep to the single-device or sharded path; both
    return per-row summary dicts with the grid row as the trailing axis
    of each (B,) / (F, B) leaf, bit-identical to each other.

    ``segments`` (one config id per grid row, ids ``0 .. C-1`` each
    present) adds the ``latency_hist`` leaf, ``(..., C, NB)``: the rows'
    latency histograms merged per config inside the device program."""
    seg = None if segments is None else np.asarray(segments, np.int32)
    nc = 0 if seg is None else int(seg.max()) + 1
    if mesh is None:
        return _sweep_fused(prof, workload, dispatch, drift, cloud, faults,
                            grid, None if seg is None else jnp.asarray(seg),
                            n_requests=n_requests, warmup=warmup,
                            num_configs=nc)
    n_dev = int(mesh.devices.size)
    padded, n = pad_leading(grid, n_dev)
    if seg is not None:
        # padded rows get id C, which the merge drops
        seg = np.concatenate([seg, np.full((-n) % n_dev, nc, np.int32)])
    fn = _sweep_sharded_fn(mesh, n_requests, warmup, prof.is_stacked, nc)
    out = fn(prof, workload, dispatch, drift, cloud, faults,
             ConfigGrid(*map(jnp.asarray, padded)),
             None if seg is None else jnp.asarray(seg))
    # gathered onto one device: a later reduction over the config axis
    # (the user-block segment folds) would otherwise be partitioned over
    # the shards and reassociated, moving means by an ULP
    with spans.span("repro.sweep.gather"):
        spans.count("sweep.gather_bytes",
                    sum(v.nbytes for v in out.values()))
        out = jax.device_put(out, jax.devices()[0])
    return {k: v if k == "latency_hist" else v[..., :n]
            for k, v in out.items()}


def simulate(prof: ProfileTable, cfg: SimConfig,
             workload: WorkloadSource | None = None,
             dispatch: DispatchEngine | None = None,
             drift: DriftSchedule | None = None):
    """Deprecated: use ``repro.core.scenario.records(Scenario(...))``
    (one spec object instead of a config + three parallel kwargs). Same
    contract as :func:`_simulate`."""
    _warn_legacy("simulate",
                 "use repro.core.scenario.records(Scenario(...))")
    return _simulate(prof, cfg, workload, dispatch, drift)


def _simulate(prof: ProfileTable, cfg: SimConfig,
              workload: WorkloadSource | None = None,
              dispatch: DispatchEngine | None = None,
              drift: DriftSchedule | None = None,
              cloud=None, faults=None):
    """Returns a dict of per-request record arrays (length n_requests).
    Single-fleet only — stacked tables go through :func:`simulate_batch` /
    :func:`sweep_grid`, which vmap the fleet axis. ``workload`` /
    ``dispatch`` default to the config's own (``cfg.workload`` /
    ``cfg.dispatch``), else the Markov chain and static dispatch;
    ``drift`` optionally perturbs the true profile mid-run
    (:class:`repro.core.dispatch.DriftSchedule`); ``cloud`` is the
    :class:`~repro.core.cloud.CloudMeta` of an offload-extended ``prof``
    (``CloudTier.extend``), or ``None`` for an edge-only fleet;
    ``faults`` the resolved :class:`~repro.core.faults.FaultMeta` of a
    :class:`~repro.core.faults.FaultSchedule`, or ``None``."""
    if prof.is_stacked:
        raise ValueError("simulate() takes a single (P, G) ProfileTable; "
                         "pass stacked tables to simulate_batch/sweep_grid")
    workload = _resolve_workload(workload, (cfg,))
    dispatch = _resolve_dispatch(dispatch, (cfg,))
    true0, rng, phase = workload.init_draws(
        cfg.seed, cfg.stickiness, n_groups=prof.n_groups,
        n_users=cfg.n_users)
    g = ConfigGrid(
        policy_code=jnp.asarray(POLICY_CODES[cfg.policy], i32),
        n_users=jnp.asarray(cfg.n_users, i32),
        gamma=jnp.asarray(cfg.gamma, f32),
        delta=jnp.asarray(cfg.delta, f32),
        stickiness=jnp.asarray(cfg.stickiness, f32),
        oracle=jnp.asarray(cfg.oracle_estimator, bool),
        rng=jnp.asarray(rng), true0=jnp.asarray(true0, i32),
        phase=jnp.asarray(phase, i32))
    return _simulate_one(prof, workload, dispatch, drift, cloud, faults,
                         g, n_requests=cfg.n_requests)


def simulate_batch(prof: ProfileTable, grid: ConfigGrid, n_requests: int,
                   workload: WorkloadSource | None = None,
                   dispatch: DispatchEngine | None = None,
                   drift: DriftSchedule | None = None):
    """Deprecated: use ``repro.core.scenario.records(Scenario, Sweep)``
    (named axes instead of a flat grid). Same contract as
    :func:`_simulate_batch`."""
    _warn_legacy("simulate_batch",
                 "use repro.core.scenario.records(Scenario, Sweep)")
    return _simulate_batch(prof, grid, n_requests, workload, dispatch,
                           drift)


def _simulate_batch(prof: ProfileTable, grid: ConfigGrid, n_requests: int,
                    workload: WorkloadSource | None = None,
                    dispatch: DispatchEngine | None = None,
                    drift: DriftSchedule | None = None,
                    cloud=None, faults=None):
    """Run every config in ``grid`` as ONE vmapped scan in ONE jit.

    Args:
      prof: fleet profile, either a single ``(P, G)`` table or a stacked
        ``(F, P, G)`` ensemble (``repro.core.profiles.stack_profiles``);
        a stacked table runs every fleet × config combination in the same
        fused program.
      grid: struct-of-arrays batch from :func:`make_grid`, leading dim B.
      n_requests: scan length. Required (no default) and must match the
        configs the grid was built from — the grid carries only traced
        leaves, not scan shapes.
      workload: the scene-complexity source the grid was built with
        (``make_grid(..., workload=...)``); defaults to the Markov
        chain. Must match the build-time source — a grid whose ``phase``
        leaf is nonzero (a trace draw) is rejected under the Markov
        default rather than silently re-interpreted.
      dispatch: dispatch-state engine (``repro.core.dispatch``;
        :class:`StaticDispatch` by default). Its ``DispatchState`` pytree
        rides in the scan carry, so online engines vmap over configs and
        shard over meshes unchanged.
      drift: optional :class:`~repro.core.dispatch.DriftSchedule`
        perturbing the TRUE profile per dispatch step — the scenario hook
        for throttling / model-swap experiments.

    Returns:
      Dict of float32/int32 record arrays with leading dims
      ``(B, n_requests)`` — ``(F, B, n_requests)`` when ``prof`` is
      stacked. Row ``b`` (of fleet ``f``) is bit-identical to
      ``simulate(prof_f, cfg_b)`` for the matching config: padding users
      to ``n_users_max`` and batching over configs/fleets never changes
      any config's trajectory.
    """
    workload = _resolve_workload(workload)
    dispatch = _resolve_dispatch(dispatch)
    if isinstance(workload, MarkovWorkload) and bool(grid.phase.any()):
        raise ValueError(
            "grid carries nonzero workload phase offsets (built with a "
            "trace source) but simulate_batch resolved the Markov "
            "default; pass the grid's own workload= explicitly")
    return _simulate_vmapped(prof, workload, dispatch, drift, cloud,
                             faults, grid, n_requests=n_requests)


def _summarize_core(recs, prof: ProfileTable, warmup: int, cloud=None, *,
                    with_hist: bool = False):
    n = recs["latency"].shape[0]
    sl = {k: v[warmup:] for k, v in recs.items()}
    makespan = jnp.max(sl["t_arrival"] + sl["latency"]) \
        - jnp.min(sl["t_arrival"])
    n_eff = n - warmup
    floor = prof.floor_mw if prof.floor_mw is not None \
        else jnp.zeros((prof.n_pairs,))
    floor_mwh = jnp.sum(floor) * makespan / 3600.0
    out = {
        "latency_ms": 1000.0 * jnp.mean(sl["latency"]),
        "latency_p90_ms": 1000.0 * jnp.percentile(sl["latency"], 90),
        "throughput_rps": n_eff / makespan,
        "energy_mwh": jnp.mean(sl["energy"]) + floor_mwh / n_eff,
        "energy_compute_mwh": jnp.mean(sl["energy"]),
        "map": jnp.mean(sl["map"]),
        "estimator_acc": jnp.mean(sl["correct_group"]),
        "makespan_s": makespan,
    }
    if cloud is not None:
        out["offload_share"] = jnp.mean(
            cloud.is_cloud[sl["server"]].astype(f32))
    if "slo_violation" in recs:
        # fault-plane availability metrics (records carry these keys
        # only when a FaultSchedule is active)
        out["slo_violation_share"] = jnp.mean(sl["slo_violation"])
        out["failed_share"] = jnp.mean(sl["failed"])
        out["latency_p99_ms"] = 1000.0 * jnp.percentile(sl["latency"], 99)
    if with_hist:
        out["latency_hist"] = latency_histogram(sl["latency"])
    return out


def summarize(recs, prof: ProfileTable, cfg: SimConfig):
    """Aggregate a record set into the paper's Fig. 4/5 metrics.
    Single-fleet only (a stacked table's floor term would silently sum
    over fleets); use :func:`summarize_batch` for ensembles."""
    if prof.is_stacked:
        raise ValueError("summarize() takes a single (P, G) ProfileTable; "
                         "use summarize_batch for stacked tables")
    n = recs["latency"].shape[0]
    return _summarize_core(recs, prof, int(n * cfg.warmup_frac))


@functools.partial(jax.jit, static_argnames=("warmup",))
def summarize_batch(recs, prof: ProfileTable, *, warmup: int):
    """Batched :func:`summarize` over ``(B, n_requests)`` record arrays
    (``(F, B, n_requests)`` with a stacked ``prof`` — the fleet axis of
    ``recs`` must match ``prof.n_fleets``). ``warmup`` is the number of
    leading records dropped per config, usually
    ``int(n_requests * warmup_frac)``. Returns ``(B,)`` / ``(F, B)``
    float32 metric vectors; reductions are per config, so values match the
    scalar :func:`summarize` to float32 tolerance (vmap may reassociate)."""
    def per_fleet(r, pf):
        return jax.vmap(lambda r1: _summarize_core(r1, pf, warmup))(r)

    if prof.is_stacked:
        return jax.vmap(per_fleet)(recs, prof)
    return per_fleet(recs, prof)


def run_policy(prof: ProfileTable, policy: str, n_users: int,
               n_requests: int = 2000, gamma: float = 0.5,
               delta: float = 20.0, seed: int = 0, stickiness: float = 0.85,
               workload: WorkloadSource | None = None,
               dispatch: DispatchEngine | None = None,
               drift: DriftSchedule | None = None):
    """Deprecated: use ``repro.core.scenario.run(Scenario(...))`` and
    read ``Results.scalar(metric)``."""
    _warn_legacy("run_policy",
                 "use repro.core.scenario.run(Scenario(...))")
    cfg = SimConfig(n_users=n_users, n_requests=n_requests, policy=policy,
                    gamma=gamma, delta=delta, seed=seed,
                    stickiness=stickiness, workload=workload,
                    dispatch=dispatch)
    recs = _simulate(prof, cfg, drift=drift)
    out = summarize(recs, prof, cfg)
    return {k: float(v) for k, v in out.items()}


SWEEP_AXES = ("policy", "users", "gamma", "delta", "oracle", "seed")


def _sweep_grid_impl(prof, policies, user_levels, gammas, deltas, oracle,
                     seeds, n_requests, stickiness, warmup_frac, mesh,
                     workload, dispatch, drift):
    """The legacy Cartesian sweep AS a Scenario + Sweep: the kwarg axes
    map 1:1 onto Scenario fields (the SWEEP_AXES tuple is just the
    declaration order), and the scenario engine runs the identical
    config product through the identical fused program — bit-identical
    to the pre-scenario engine (golden fixtures pin it)."""
    from repro.core import scenario as SC
    sc = SC.Scenario(profile=prof, n_requests=n_requests,
                     stickiness=stickiness, warmup_frac=warmup_frac,
                     workload=workload, dispatch=dispatch, drift=drift)
    sw = SC.Sweep(policy=tuple(policies), n_users=tuple(user_levels),
                  gamma=tuple(gammas), delta=tuple(deltas),
                  oracle_estimator=tuple(oracle), seed=tuple(seeds))
    return dict(SC.run(sc, sw, mesh=mesh).metrics)


def sweep_grid(prof: ProfileTable, policies=("MO",), user_levels=(15,),
               gammas=(0.5,), deltas=(20.0,), oracle=(False,),
               seeds=(0, 1, 2), n_requests: int = 2000,
               stickiness: float = 0.85, warmup_frac: float = 0.1,
               mesh=None, workload: WorkloadSource | None = None,
               dispatch: DispatchEngine | None = None,
               drift: DriftSchedule | None = None):
    """Cartesian-product sweep as a single fused device program.

    Deprecated: this is now a thin shim over the Scenario path — use
    ``repro.core.scenario.run(Scenario(...), Sweep(...))``, which sweeps
    ANY Scenario field by name (not just these six axes) and returns
    named-axis :class:`~repro.core.scenario.Results`. Results here stay
    bit-identical to the pre-scenario engine.

    Args:
      prof: fleet profile; a stacked ``(F, P, G)`` ensemble sweeps every
        fleet over the same grid in one program.
      policies / user_levels / gammas / deltas / oracle / seeds: the grid
        axes (axis order :data:`SWEEP_AXES`); their Cartesian product is
        flattened into one :func:`make_grid` batch of
        ``B = prod(axis lengths)`` configs.
      n_requests, stickiness, warmup_frac: shared scalar parameters (scan
        shape / chain stickiness / warmup fraction) for every config.
      mesh: optional ``jax.sharding.Mesh`` (e.g.
        ``repro.launch.mesh.make_sweep_mesh()``). When given, the flat
        config axis is sharded over every mesh axis via ``shard_map``,
        padding B up to a multiple of the device count; results are
        bit-identical to the single-device path.
      workload: scene-complexity source shared by every config — the
        Markov chain by default, or a recorded trace
        (``repro.data.traces.TraceWorkload``). Orthogonal to ``mesh``
        and fleet stacking.
      dispatch: dispatch-state engine shared by every config —
        :class:`~repro.core.dispatch.StaticDispatch` by default, or
        :class:`~repro.core.dispatch.OnlineDispatch` for online-EWMA
        adaptation. Orthogonal to ``mesh``, ``workload`` and fleet
        stacking.
      drift: optional :class:`~repro.core.dispatch.DriftSchedule`
        perturbing the TRUE profile mid-run for every config (thermal
        throttling / model swap scenarios).

    Returns:
      ``{metric: float64 ndarray}`` with shape ``(len(policies),
      len(user_levels), len(gammas), len(deltas), len(oracle),
      len(seeds))``, with a leading fleet axis when ``prof`` is stacked.
      The whole grid is one ``vmap(simulate + summarize)`` under one jit;
      the trace is cached across calls with the same batch size, scan
      length, and mesh.
    """
    _warn_legacy("sweep_grid", "use repro.core.scenario.run(Scenario, "
                 "Sweep) — any Scenario field is a sweep axis")
    return _sweep_grid_impl(prof, policies, user_levels, gammas, deltas,
                            oracle, seeds, n_requests, stickiness,
                            warmup_frac, mesh, workload, dispatch, drift)


def sweep(prof: ProfileTable, policies, user_levels, n_requests: int = 2000,
          gamma: float = 0.5, delta: float = 20.0, seeds=(0, 1, 2)):
    """Full Fig. 4-style sweep; returns {policy: {metric: [per-level mean]}}.
    Each configuration runs ``len(seeds)`` times (paper: 3 repetitions).

    Deprecated: use ``repro.core.scenario.run(Scenario, Sweep(policy=...,
    n_users=..., seed=...))`` and ``Results.mean(metric, over="seed")``.
    Single-fleet only — the per-policy dict layout has no fleet axis."""
    _warn_legacy("sweep", "use repro.core.scenario.run(Scenario, Sweep) "
                 "and Results.mean(metric, over='seed')")
    if prof.is_stacked:
        raise ValueError("sweep() returns a per-policy dict with no fleet "
                         "axis; pass stacked ProfileTables to sweep_grid()")
    m = _sweep_grid_impl(prof, policies=policies, user_levels=user_levels,
                         gammas=(gamma,), deltas=(delta,), oracle=(False,),
                         seeds=seeds, n_requests=n_requests,
                         stickiness=0.85, warmup_frac=0.1, mesh=None,
                         workload=None, dispatch=None, drift=None)
    out: dict[str, dict[str, list[float]]] = {}
    for i, pol in enumerate(policies):
        out[pol] = {k: [float(np.mean(v[i, j, 0, 0, 0, :]))
                        for j in range(len(user_levels))]
                    for k, v in m.items()}
    return out
