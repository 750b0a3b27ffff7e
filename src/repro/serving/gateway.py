"""The request plane's router: micro-batched admission, device-resident
state.

:class:`WindowedGateway` is the serving plane's primary router. It admits
requests in *windows*: one jitted device program routes the whole window —
estimator gather (last detected count per stream, a device-resident
``(n_streams,)`` array, not a host dict), Algorithm-1 scoring with
intra-window queue feedback, and the dispatch-state advance — so the
router's cost per request is a window's worth of XLA work divided by W
instead of a Python loop body. The MO hot path runs the fused ``moscore``
kernel (``repro.kernels.moscore``), backend-aware: the compiled
invariant-hoisted Pallas kernel on TPU, the bit-identical hoisted XLA
scan elsewhere (``backend="auto"``; the ``REPRO_MOSCORE_BACKEND`` env
var overrides the auto choice, e.g. ``int8`` for quantized belief
tables under the bounded-mismatch contract — see ``docs/kernels.md``).
Every other policy routes through the dispatch
engine's :meth:`~repro.core.dispatch.DispatchEngine.select_window` scan —
the SAME ``init``/``select``/``observe`` code the batched simulator
threads through its scan, so simulation and serving still run one
stateful code path.

Observations flow back in windows too: ``observe_window`` folds a batch
of completed-request measurements into the dispatch engine's belief state
(one fused program, via the engine's ``observe_window`` hook), and
``observe_detections_window`` scatters detected counts into the
device-resident estimator state (duplicate streams resolve to the
*latest* entry, matching a sequential replay).

Per-request randomness is derived by ``fold_in(key, request_index)``
from an absolute request counter — NOT by chain-splitting a key per
call — so the key stream is invariant to how requests are partitioned
into windows: two gateways with the same seed and different window sizes
route identical request streams identically (regression-tested).

A gateway can be built straight from a
:class:`~repro.core.scenario.Scenario` — ``WindowedGateway(scenario)`` —
so simulation and serving share ONE config object: the scenario's
profile, policy, γ, Δ, dispatch engine and seed all apply to knobs left
at their constructor defaults, while any explicitly passed non-default
kwarg (``policy=``, ``gamma=``, ``dispatch=``, ...) wins.

:class:`Gateway` — the original per-request router — remains as a thin
deprecation-warned shim: ``route`` is ``route_window`` with a window of
one, proven bit-identical to the windowed path by
``tests/test_serving_plane.py``. See ``docs/serving.md`` for the
architecture guide and the migration table.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import spans
from repro.core import estimator as EST
from repro.core.dispatch import (DispatchEngine, OnlineDispatch,
                                 StaticDispatch)
from repro.core.hierarchy import hierarchical_select, pod_aggregate
from repro.core.policies import POLICY_CODES
from repro.core.profiles import ProfileTable
from repro.kernels.moscore import moscore_route, resolve_backend

i32 = jnp.int32
f32 = jnp.float32


@dataclass
class WindowedGateway:
    """Windowed (micro-batched) router over a heterogeneous fleet.

    ``prof`` is a :class:`~repro.core.profiles.ProfileTable` or a
    :class:`~repro.core.scenario.Scenario` (resolved in
    ``__post_init__``; its policy/γ/Δ/dispatch/seed apply to knobs left
    at their defaults). ``n_streams`` is the estimator-state capacity
    (stream ids must stay below it); ``backend`` picks the MO routing
    kernel (``"auto"`` | one of ``repro.kernels.moscore.BACKENDS`` —
    the fp32 backends are interchangeable bit-for-bit; ``"int8"``
    quantizes the belief tables handed to the kernel each window and
    routes under the bounded-mismatch contract of
    ``repro.core.quant``).

    ``cloud`` is an optional :class:`~repro.core.cloud.CloudTier`: the
    served fleet is extended with its remote pairs
    (``CloudTier.extend``), and latency-aware routing sees the uplink
    congestion penalty — which means cloud-active MO routes through the
    generic ``select_window`` scan (the fused ``moscore`` kernel scores
    raw tables and has no penalty hook). A scenario-built gateway
    adopts the scenario's cloud tier like any other knob.

    ``pods`` turns on hierarchical (two-level) routing
    (``repro.core.hierarchy``): a per-pair pod-id vector partitions the
    fleet, level 1 picks a pod by Algorithm 1 over pod-aggregate
    profiles with queue totals snapshotted at WINDOW ADMISSION (stale
    within the window — the price of decentralisation), level 2 runs
    Algorithm 1 inside the pod with exact in-window queue feedback.
    With a cloud tier, a ``pods`` vector covering only the local pairs
    puts the remote pairs in their own extra pod.

    ``faults`` is an optional :class:`~repro.core.faults.FaultSchedule`:
    a *visible* schedule routes every window through the generic
    ``select_window`` scan with the per-request health mask
    (``health_at`` of the absolute request index — window-partition
    invariant like the key stream), masking down pairs at the
    accuracy-feasibility stage with the degraded fallback; an
    *invisible* one leaves the router blind (the serving plane's truth
    model still faults). Adopted from a scenario like every other knob.
    Hierarchical (``pods``) routing has no fault mask yet — combining
    them raises."""

    prof: ProfileTable
    policy: str = "MO"
    gamma: float = 0.5
    delta: float = 20.0
    online: bool = False      # shorthand for dispatch=OnlineDispatch()
    seed: int = 1234          # seeds the per-request key stream (RND)
    dispatch: DispatchEngine | None = None
    n_streams: int = 1024
    backend: str = "auto"
    cloud: Any = None         # CloudTier | None — edge-to-cloud tier
    faults: Any = None        # FaultSchedule | None — the fault plane
    pods: Any = None          # (P,) pod ids | None — hierarchical routing
    _counts: Any = field(default=None, repr=False)
    _dstate: Any = field(default=None, repr=False)
    _step: int = field(default=0, repr=False)

    def __post_init__(self):
        from repro.core.scenario import Scenario
        if isinstance(self.prof, Scenario):
            sc = self.prof
            self.prof = sc.resolve_profile()
            # the scenario's knobs apply to every field still at its
            # constructor default; an explicitly passed kwarg wins, so
            # WindowedGateway(sc, policy="LT") tweaks one knob on a
            # shared spec (passing a kwarg AT its default defers to the
            # scenario — a dataclass cannot see the difference)
            for name, default, value in (
                    ("policy", "MO", sc.policy),
                    ("gamma", 0.5, sc.gamma),
                    ("delta", 20.0, sc.delta),
                    ("seed", 1234, sc.seed)):
                if getattr(self, name) == default:
                    setattr(self, name, value)
            # same precedence for the engine: explicit dispatch= wins; a
            # scenario that configures its own engine wins over the
            # online= shorthand (silently swapping a tuned engine for a
            # default OnlineDispatch() would be worse)
            if self.dispatch is None \
                    and not (self.online and sc.dispatch is None):
                self.dispatch = sc.resolve_dispatch()
            # estimator-state capacity follows the scenario's fleet
            # size: a 10^5-user scenario gets 10^5 stream slots without
            # the caller sizing state by hand. Monotone — an explicit
            # larger n_streams= wins, the default never shrinks
            if self.n_streams == 1024:
                self.n_streams = max(self.n_streams, sc.n_users)
            if self.cloud is None:
                self.cloud = sc.cloud
            if self.faults is None:
                self.faults = sc.faults
        if self.prof.is_stacked:
            raise ValueError("gateway serves one fleet; scenario/profile "
                             "is a stacked ensemble")
        self._cloud_meta = None
        if self.cloud is not None:
            self.prof, self._cloud_meta = self.cloud.extend(self.prof)
        # fault schedules bind to the EXTENDED pair axis (a scripted
        # outage can take down a cloud pair)
        self._fault_meta = None
        if self.faults is not None and self.faults.active:
            if self.pods is not None:
                raise ValueError(
                    "hierarchical (pods=) routing has no fault mask yet — "
                    "route the flat fleet under a FaultSchedule")
            self._fault_meta = self.faults.resolve(self.prof.n_pairs)
        self._pod_of_pair = None
        if self.pods is not None:
            if self.policy != "MO":
                raise ValueError("pods= hierarchical routing is two-level "
                                 "Algorithm 1 — MO policy only")
            pod = np.asarray(self.pods, np.int32)
            n_cloud = 0 if self._cloud_meta is None else int(
                np.asarray(self._cloud_meta.is_cloud).sum())
            if n_cloud and pod.shape == (self.prof.n_pairs - n_cloud,):
                # the remote pairs form their own pod under the global
                # balancer — the natural edge-clusters-plus-cloud shape
                pod = np.concatenate(
                    [pod, np.full((n_cloud,), pod.max() + 1, np.int32)])
            if pod.shape != (self.prof.n_pairs,):
                raise ValueError(
                    f"pods must give one pod id per pair "
                    f"({self.prof.n_pairs}), got shape {pod.shape}")
            self._pod_of_pair = jnp.asarray(pod, i32)
        if self.dispatch is None:
            self.dispatch = OnlineDispatch() if self.online \
                else StaticDispatch()
        self.online = isinstance(self.dispatch, OnlineDispatch)
        self.backend = resolve_backend(self.backend)
        self._key = jax.random.PRNGKey(self.seed)
        self._counts = jnp.zeros((self.n_streams,), i32)
        self._dstate = self.dispatch.init(self.prof)
        self._step = 0

        code = POLICY_CODES[self.policy]
        engine, prof = self.dispatch, self.prof
        n_groups, n_streams = prof.n_groups, self.n_streams
        gamma, delta = float(self.gamma), float(self.delta)
        backend, base_key = self.backend, self._key
        cloud_meta, pod_of_pair = self._cloud_meta, self._pod_of_pair
        penalty_fn = None if cloud_meta is None else cloud_meta.penalty
        fault_meta = self._fault_meta

        # each program below counts its traces as ``gateway.retrace``
        # (``fn=`` its name) while a profile is being captured, so a
        # trace says which one recompiled
        @jax.jit
        def _route_fused(state, counts, q0, ids):
            spans.count("gateway.retrace", fn="_route_fused")
            # MO fast path: estimator gather + the fused routing kernel
            # against the engine's current belief tables; rr advances by
            # W exactly as W select() calls would have advanced it
            gs = EST.group_of_count(counts[ids], n_groups)
            tbl = engine.tables(state, prof)
            pairs, q = moscore_route(tbl.T, tbl.E, tbl.mAP, gs,
                                     q0.astype(f32), delta=delta,
                                     gamma=gamma, backend=backend)
            state = {**state, "rr": state["rr"] + ids.shape[0]}
            return pairs, gs, q, state

        @jax.jit
        def _route_scan(state, counts, q0, ids, step0):
            spans.count("gateway.retrace", fn="_route_scan")
            # generic path (every policy): the engine's select_window
            # scan, with per-request keys folded from the ABSOLUTE
            # request index — window-partition invariant
            gs = EST.group_of_count(counts[ids], n_groups)
            idx = step0 + jnp.arange(ids.shape[0], dtype=i32)
            keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(idx)
            pairs, q, state = engine.select_window(
                state, prof, code, gs, q0.astype(f32), keys,
                jnp.asarray(gamma, f32), jnp.asarray(delta, f32),
                penalty_fn=penalty_fn)
            return pairs, gs, q, state

        @jax.jit
        def _route_scan_masked(state, counts, q0, ids, step0):
            spans.count("gateway.retrace", fn="_route_scan_masked")
            # fault-visible path: the same scan, plus the per-request
            # health mask drawn from the ABSOLUTE request index — fault
            # realizations are window-partition invariant exactly like
            # the key stream
            gs = EST.group_of_count(counts[ids], n_groups)
            idx = step0 + jnp.arange(ids.shape[0], dtype=i32)
            keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(idx)
            healths = jax.vmap(fault_meta.health_at)(idx)
            pairs, q, state = engine.select_window(
                state, prof, code, gs, q0.astype(f32), keys,
                jnp.asarray(gamma, f32), jnp.asarray(delta, f32),
                penalty_fn=penalty_fn, healths=healths)
            return pairs, gs, q, state

        @jax.jit
        def _route_pods(state, counts, q0, ids):
            spans.count("gateway.retrace", fn="_route_pods")
            # hierarchical path: pod queue totals are snapshotted ONCE at
            # window admission (stale inside the window); level-2 exact
            # queues get in-window feedback like every other path
            gs = EST.group_of_count(counts[ids], n_groups)
            tbl = engine.tables(state, prof)
            pod_tbl = pod_aggregate(tbl, pod_of_pair)
            n_pods = pod_tbl.n_pairs
            q_pod0 = jax.ops.segment_sum(q0.astype(f32), pod_of_pair,
                                         num_segments=n_pods)

            def step(q, g):
                pen = None if cloud_meta is None \
                    else cloud_meta.penalty(g, q)
                p, _pod = hierarchical_select(
                    tbl, pod_tbl, pod_of_pair, g, q, q_pod0,
                    delta=delta, gamma=gamma, penalty=pen)
                return q.at[p].add(1.0), p.astype(i32)

            q, pairs = jax.lax.scan(step, q0.astype(f32), gs)
            state = {**state, "rr": state["rr"] + ids.shape[0]}
            return pairs, gs, q, state

        @jax.jit
        def _obs_counts(counts, ids, cnts):
            spans.count("gateway.retrace", fn="_obs_counts")
            # last-write-wins scatter: scatter-MAX of the window index
            # per stream is well-defined under duplicates (unlike
            # .at[].set), so the result is bit-identical to a sequential
            # per-request replay
            w = ids.shape[0]
            pos = jnp.full((n_streams,), -1, i32).at[ids].max(
                jnp.arange(w, dtype=i32))
            latest = cnts[jnp.clip(pos, 0)]
            return jnp.where(pos >= 0, latest, counts)

        @jax.jit
        def _observe_win(state, pairs, groups, t_ms, e_mwh):
            spans.count("gateway.retrace", fn="_observe_win")
            return engine.observe_window(state, pairs, groups, t_ms,
                                         e_mwh)

        @jax.jit
        def _observe_one(state, p, g, t_ms, e_mwh):
            spans.count("gateway.retrace", fn="_observe_one")
            return engine.observe(state, p, g, t_ms, e_mwh)

        self._route_fused = _route_fused
        self._route_scan = _route_scan
        self._route_scan_masked = _route_scan_masked
        self._route_pods = _route_pods
        self._obs_counts = _obs_counts
        self._observe_win = _observe_win
        self._observe_one = _observe_one

    # -- estimator state ----------------------------------------------------

    def _check_streams(self, ids: np.ndarray):
        if ids.size and (ids.min() < 0 or ids.max() >= self.n_streams):
            raise ValueError(
                f"stream id out of range [0, {self.n_streams}) — raise "
                f"n_streams= (gateway estimator-state capacity)")

    def observe_detections_window(self, stream_ids, detected_counts):
        """Scatter a batch of detected object counts into the
        device-resident estimator state (one program; the latest entry
        wins for a stream that completes twice in one window)."""
        ids = np.asarray(stream_ids, np.int64)
        self._check_streams(ids)
        self._counts = self._obs_counts(
            self._counts, jnp.asarray(ids, i32),
            jnp.asarray(np.asarray(detected_counts), i32))

    # -- dispatch-state observation -----------------------------------------

    def observe_window(self, pairs, groups, latency_ms,
                       energy_mwh=None) -> None:
        """Fold a completed window's measurements into the dispatch
        state via the engine's ``observe_window`` hook — one fused device
        program (skipped entirely for non-adaptive engines: the hot
        serving path pays nothing under :class:`StaticDispatch`)."""
        if not self.dispatch.adaptive:
            return
        self._dstate = self._observe_win(
            self._dstate, jnp.asarray(np.asarray(pairs), i32),
            jnp.asarray(np.asarray(groups), i32),
            jnp.asarray(np.asarray(latency_ms), f32),
            None if energy_mwh is None
            else jnp.asarray(np.asarray(energy_mwh), f32))

    def _tables(self) -> ProfileTable:
        return self.dispatch.tables(self._dstate, self.prof)

    # -- decisions ----------------------------------------------------------

    def route_window(self, stream_ids, queue_depths):
        """Route one admission window in one jitted call.

        ``stream_ids``: (W,) ints below ``n_streams``; ``queue_depths``:
        (P,) live queue depths at admission. Returns ``(pairs (W,),
        est_groups (W,), q_after (P,))`` as device arrays — queue
        feedback is applied *within* the window (decision w+1 sees
        decision w's bump), and ``q_after`` is the depths to thread into
        the next window when no executor feedback arrives in between.
        Bit-identical for any window partition of the same request
        stream (the per-request :class:`Gateway` shim is the W=1 case).
        """
        ids = np.asarray(stream_ids, np.int64)
        self._check_streams(ids)
        ids_d = jnp.asarray(ids, i32)
        q0 = jnp.asarray(queue_depths, f32)   # no-copy for device arrays
        if self._pod_of_pair is not None:
            pairs, gs, q, self._dstate = self._route_pods(
                self._dstate, self._counts, q0, ids_d)
        elif self._fault_meta is not None and self._fault_meta.visible:
            # visible faults need the per-request health mask, which the
            # fused kernel (one mask per window) cannot express — the
            # generic scan carries it (cloud precedent)
            pairs, gs, q, self._dstate = self._route_scan_masked(
                self._dstate, self._counts, q0, ids_d,
                jnp.asarray(self._step, i32))
        elif self.policy == "MO" and self._cloud_meta is None:
            # the fused kernel scores raw tables with no penalty hook;
            # cloud-active MO takes the generic scan for the congestion
            # term (bit-identical scoring otherwise)
            pairs, gs, q, self._dstate = self._route_fused(
                self._dstate, self._counts, q0, ids_d)
        else:
            pairs, gs, q, self._dstate = self._route_scan(
                self._dstate, self._counts, q0, ids_d,
                jnp.asarray(self._step, i32))
        self._step += int(ids.shape[0])
        return pairs, gs, q


class Gateway(WindowedGateway):
    """Per-request shim over the windowed request plane (deprecated).

    ``route`` / ``observe_detections`` / ``observe_latency`` are the
    W=1 forms of the windowed hooks — bit-identical to
    :class:`WindowedGateway` on the same request stream (asserted in
    ``tests/test_serving_plane.py``), just W device programs where the
    windowed path needs one. New code should admit windows; see the
    migration table in ``docs/serving.md``."""

    def __post_init__(self):
        from repro.core.scenario import LegacyAPIWarning
        warnings.warn(
            "repro.serving.Gateway routes one request per device program; "
            "it is a deprecated shim over the windowed request plane — "
            "use WindowedGateway.route_window / ServingPlane (see "
            "docs/serving.md for the migration table)",
            LegacyAPIWarning, stacklevel=3)
        super().__post_init__()

    # -- estimator ----------------------------------------------------------
    def estimate_group(self, stream_id: int) -> int:
        return int(EST.group_of_count(self._counts[int(stream_id)],
                                      self.prof.n_groups))

    def observe_detections(self, stream_id: int, detected_count: int) -> None:
        self.observe_detections_window([stream_id], [detected_count])

    def observe_latency(self, pair: int, group: int, latency_ms: float,
                        energy_mwh: float | None = None) -> None:
        """Fold one completed request's measurements into the dispatch
        state (skipped entirely for non-adaptive engines)."""
        if not self.dispatch.adaptive:
            return
        self._dstate = self._observe_one(
            self._dstate, jnp.asarray(pair, i32), jnp.asarray(group, i32),
            jnp.asarray(latency_ms, f32),
            None if energy_mwh is None else jnp.asarray(energy_mwh, f32))

    # -- decisions ----------------------------------------------------------
    def route(self, stream_id: int, queue_depths) -> tuple[int, int]:
        """One request -> (pair, est_group): a window of one."""
        pairs, gs, _q = self.route_window([stream_id], queue_depths)
        return int(pairs[0]), int(gs[0])
