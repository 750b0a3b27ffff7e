"""Declarative scenarios: ONE spec object through sim, serving and
benchmarks.

The paper's evaluation is a grid of *scenarios* — device fleets ×
workload dynamics × dispatch policies × drift — but the engine used to
express a scenario as four parallel kwargs (``workload=``, ``dispatch=``,
``drift=``, ``mesh=``) threaded through six signatures, and sweep axes
were the hardcoded ``SWEEP_AXES`` 6-tuple. This module replaces that
with three objects:

  * :class:`Scenario` — a frozen, JSON-serializable bundle of everything
    one simulated (or served) configuration needs: the fleet profile, the
    scene-complexity :class:`~repro.core.workload.WorkloadSource`, the
    :class:`~repro.core.dispatch.DispatchEngine`, an optional
    :class:`~repro.core.dispatch.DriftSchedule`, a mesh spec, and the
    per-config knobs (policy, concurrency, γ, Δ, stickiness, seed, ...).
    ``to_json``/``from_json`` round-trip it exactly and
    :attr:`Scenario.hash` fingerprints it — benchmark artifacts embed the
    spec so regression gates compare like-for-like.
  * :class:`Sweep` — sweep axes declared **by field name**:
    ``Sweep(policy=("MO", "LT"), stickiness=(0.5, 0.85))`` sweeps any
    ``Scenario`` field, not just the six the legacy tuple hardcoded.
    Config-leaf axes (:data:`CONFIG_AXES`) fuse into ONE batched device
    program exactly like the legacy engine; a ``drift`` axis over
    same-shape schedules fuses as an extra vmapped batch axis; component
    axes (``workload``, ``dispatch``, ...) run one fused program per
    value.
  * :class:`Results` — named-axis summaries: every metric is an ndarray
    whose axes carry the sweep's field names and coordinate values
    (``res.sel("latency_ms", policy="MO", n_users=15)``), so callers
    never reshape flattened config rows again.

The single entry point is :func:`run`; :func:`records` returns the
per-request record arrays for a scenario (the old ``simulate``). The
legacy kwarg entry points of ``repro.core.simulator`` are deprecation-
warned shims over this path and stay bit-identical (the golden fixtures
of ``tests/`` pin that), and ``repro.serving.gateway.Gateway`` accepts a
``Scenario`` directly, so simulation and serving share one config
object. See ``docs/sweep_engine.md`` for the architecture guide and the
legacy-kwarg migration table.
"""

from __future__ import annotations

import functools
import hashlib
import itertools
import json
from dataclasses import dataclass, replace
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import spans
from repro.core import simulator as SIM
from repro.core.cloud import CloudTier
from repro.core.faults import FaultSchedule
from repro.core.dispatch import (DispatchEngine, DriftSchedule,
                                 OnlineDispatch, StaticDispatch)
from repro.core.policies import POLICY_CODES
from repro.core.profiles import ProfileTable, paper_fleet
from repro.core.workload import MarkovWorkload, WorkloadSource

__all__ = ["Scenario", "Sweep", "Results", "run", "records",
           "LegacyAPIWarning", "register_profile", "PROFILE_REGISTRY",
           "CONFIG_AXES", "STATIC_AXES", "COMPONENT_AXES"]

SCHEMA = "repro-scenario/v1"


class LegacyAPIWarning(DeprecationWarning):
    """Issued by the deprecated kwarg entry points of
    ``repro.core.simulator`` (``simulate`` / ``simulate_batch`` /
    ``make_grid`` / ``sweep_grid`` / ``run_policy`` / ``sweep``). The
    tier-1 suite runs with this category escalated to an error
    (``pytest.ini``), proving in-repo callers are migrated; tests that
    pin the legacy contracts opt back in per test with
    ``@pytest.mark.filterwarnings``."""


# Named profiles a Scenario can reference symbolically (and therefore
# serialize by name instead of inlining the tables).
PROFILE_REGISTRY: dict[str, Callable[[], ProfileTable]] = {
    "paper": paper_fleet,
}


def register_profile(name: str, builder: Callable[[], ProfileTable]):
    """Register a named fleet profile so scenarios can reference it
    symbolically (``Scenario(profile=name)``) and serialize by name."""
    PROFILE_REGISTRY[str(name)] = builder


#: Scenario fields that are traced ``ConfigGrid`` leaves: axes over them
#: fuse into ONE batched device program (the flat config axis).
CONFIG_AXES = ("policy", "n_users", "gamma", "delta", "stickiness",
               "oracle_estimator", "seed")
#: Scenario fields that fix the compiled program's *shape*: axes over
#: them run one fused program per value. ``user_block`` is the user-axis
#: block size (``repro.core.useraxis``) — it changes how many block rows
#: each config expands into, a grid shape.
STATIC_AXES = ("n_requests", "warmup_frac", "user_block")
#: Scenario component fields: ``drift`` axes over same-shape schedules
#: fuse as an extra vmapped batch axis; same-shape ``profile`` axes fuse
#: as a stacked fleet axis; the rest (including ``cloud`` — each tier
#: value extends the fleet differently — and ``faults``, whose source
#: flags change the traced graph) loop one fused program per value.
COMPONENT_AXES = ("profile", "workload", "dispatch", "drift", "cloud",
                  "faults")

_SWEEPABLE = CONFIG_AXES + STATIC_AXES + COMPONENT_AXES


# ------------------------------------------------------------ Scenario --

@dataclass(frozen=True, eq=False)
class Scenario:
    """One evaluation scenario, declaratively.

    ``profile`` is either a registry name (:data:`PROFILE_REGISTRY`,
    default ``"paper"`` — the Table I/II testbed) or an explicit
    :class:`~repro.core.profiles.ProfileTable` (a stacked ensemble adds a
    leading ``fleet`` axis to every result). ``workload`` / ``dispatch``
    default to the Markov chain and static offline tables when ``None``;
    ``drift`` optionally perturbs the TRUE profile mid-run. ``mesh`` is a
    *spec*, not a device object: ``None`` (single device), ``"local"``
    (shard the config axis over every local device) or a device count.

    Scenarios are frozen and value-equal (two scenarios are ``==`` iff
    their canonical JSON specs match); :attr:`hash` is a stable
    fingerprint of that spec, embedded in benchmark artifacts so
    ``scripts/check_bench.py`` refuses to diff runs of different
    scenarios.
    """

    profile: ProfileTable | str = "paper"
    policy: str = "MO"
    n_users: int = 15
    n_requests: int = 2000
    gamma: float = 0.5
    delta: float = 20.0
    stickiness: float = 0.85
    seed: int = 0
    warmup_frac: float = 0.1
    oracle_estimator: bool = False
    workload: WorkloadSource | None = None
    dispatch: DispatchEngine | None = None
    drift: DriftSchedule | None = None
    user_block: int | None = None
    # user-axis block size (repro.core.useraxis): n_users > user_block
    # decomposes into ceil(n_users/user_block) independent balancer
    # replicas of <= user_block users, run as extra config rows and
    # segment-reduced back — the scaling path to 10^5..10^6-user fleets.
    # None (default) = one balancer, the paper's single-queue model.
    # Part of the scientific identity (it changes the physical system
    # when n_users > user_block), so it enters the spec/hash — but only
    # when set, keeping every existing scenario's hash unchanged.
    cloud: CloudTier | None = None
    # edge-to-cloud offloading tier (repro.core.cloud.CloudTier): when
    # set, the fleet is extended with remote model pairs whose profiled
    # latency/energy fold in RTT + scene-dependent transfer cost, the
    # simulator serialises uplink transfers, and latency-aware policies
    # see an uplink congestion penalty. None (default) = edge-only, the
    # paper's testbed — bit-identical to the pre-cloud engine
    # (tests/golden_cloud_pr7.json pins it). Scientific identity, so it
    # enters the spec/hash — but only when set.
    faults: FaultSchedule | None = None
    # the fault plane (repro.core.faults.FaultSchedule): device outages,
    # throttling bursts and stochastic WAN jitter, drawn per-step from
    # fold_in-keyed RNG (partition/block/shard-invariant). None
    # (default) = the always-up fleet — bit-identical to the pre-fault
    # engine (tests/golden_faults_pr9.json pins it). Scientific
    # identity, so it enters the spec/hash — but only when set.
    mesh: int | str | None = None

    def __post_init__(self):
        if isinstance(self.profile, str):
            if self.profile not in PROFILE_REGISTRY:
                raise ValueError(
                    f"unknown profile {self.profile!r}; registered: "
                    f"{sorted(PROFILE_REGISTRY)} (register_profile adds "
                    f"more)")
        elif not isinstance(self.profile, ProfileTable):
            raise TypeError("profile must be a registry name or a "
                            f"ProfileTable, got {type(self.profile)}")
        if self.policy not in POLICY_CODES:
            raise ValueError(f"unknown policy {self.policy!r}; one of "
                             f"{sorted(POLICY_CODES)}")
        if self.user_block is not None and (
                not isinstance(self.user_block, int)
                or isinstance(self.user_block, bool)
                or self.user_block <= 0):
            raise ValueError("user_block must be None or a positive int, "
                             f"got {self.user_block!r}")
        if self.cloud is not None and not isinstance(self.cloud,
                                                     CloudTier):
            raise TypeError("cloud must be None or a CloudTier, got "
                            f"{type(self.cloud)}")
        if self.faults is not None and not isinstance(self.faults,
                                                      FaultSchedule):
            raise TypeError("faults must be None or a FaultSchedule, got "
                            f"{type(self.faults)}")
        if not (self.mesh is None or self.mesh == "local"
                or (isinstance(self.mesh, int)
                    and not isinstance(self.mesh, bool)
                    and self.mesh > 0)):
            raise ValueError("mesh must be None, 'local', or a positive "
                             f"device count, got {self.mesh!r}")

    # -- resolution -----------------------------------------------------

    def resolve_profile(self) -> ProfileTable:
        if isinstance(self.profile, str):
            return PROFILE_REGISTRY[self.profile]()
        return self.profile

    def resolve_fleet(self):
        """``(prof, cloud_meta)`` — the fleet the engine actually runs:
        the resolved profile extended with the cloud tier's remote pairs
        (``CloudTier.extend``) when one is set, else ``(profile, None)``.
        """
        prof = self.resolve_profile()
        if self.cloud is None:
            return prof, None
        return self.cloud.extend(prof)

    def resolve_faults(self, n_pairs: int):
        """The :class:`~repro.core.faults.FaultMeta` bound to the
        (cloud-extended) fleet's ``n_pairs``, or ``None``."""
        if self.faults is None:
            return None
        return self.faults.resolve(n_pairs)

    def resolve_workload(self) -> WorkloadSource:
        return SIM._resolve_workload(self.workload)

    def resolve_dispatch(self) -> DispatchEngine:
        return SIM._resolve_dispatch(self.dispatch)

    def resolve_mesh(self):
        """The jax Mesh this scenario's sweeps shard over (or None)."""
        return _resolve_mesh(self.mesh)

    def to_config(self) -> "SIM.SimConfig":
        """The per-config slice of the scenario (a legacy SimConfig)."""
        return SIM.SimConfig(
            n_users=self.n_users, n_requests=self.n_requests,
            policy=self.policy, gamma=self.gamma, delta=self.delta,
            stickiness=self.stickiness, seed=self.seed,
            warmup_frac=self.warmup_frac,
            oracle_estimator=self.oracle_estimator)

    # -- serialization --------------------------------------------------

    def to_json(self) -> dict:
        """A JSON-compatible spec that :meth:`from_json` restores
        exactly. Components serialize by value (profiles by registry name
        when symbolic, inline tables otherwise; traces inline their
        counts), so a spec is self-contained."""
        spec = {
            "schema": SCHEMA,
            "profile": _profile_to_json(self.profile),
            "policy": self.policy,
            "n_users": self.n_users,
            "n_requests": self.n_requests,
            "gamma": self.gamma,
            "delta": self.delta,
            "stickiness": self.stickiness,
            "seed": self.seed,
            "warmup_frac": self.warmup_frac,
            "oracle_estimator": bool(self.oracle_estimator),
            "workload": _workload_to_json(self.workload),
            "dispatch": _dispatch_to_json(self.dispatch),
            "drift": _drift_to_json(self.drift),
            "mesh": self.mesh,
        }
        # only when set: the key's absence keeps every pre-user-axis
        # (and pre-cloud) scenario's canonical spec (and hash)
        # byte-identical
        if self.user_block is not None:
            spec["user_block"] = int(self.user_block)
        if self.cloud is not None:
            spec["cloud"] = self.cloud.to_json()
        if self.faults is not None:
            spec["faults"] = self.faults.to_json()
        return spec

    @classmethod
    def from_json(cls, spec: dict | str) -> "Scenario":
        """Inverse of :meth:`to_json` (accepts the dict or its JSON
        string); ``Scenario.from_json(s.to_json()) == s`` for every
        serializable scenario."""
        if isinstance(spec, str):
            spec = json.loads(spec)
        if spec.get("schema", SCHEMA) != SCHEMA:
            raise ValueError(f"not a {SCHEMA} spec: "
                             f"schema={spec.get('schema')!r}")
        return cls(
            profile=_profile_from_json(spec.get("profile", "paper")),
            policy=spec.get("policy", "MO"),
            n_users=int(spec.get("n_users", 15)),
            n_requests=int(spec.get("n_requests", 2000)),
            gamma=float(spec.get("gamma", 0.5)),
            delta=float(spec.get("delta", 20.0)),
            stickiness=float(spec.get("stickiness", 0.85)),
            seed=int(spec.get("seed", 0)),
            warmup_frac=float(spec.get("warmup_frac", 0.1)),
            oracle_estimator=bool(spec.get("oracle_estimator", False)),
            workload=_workload_from_json(spec.get("workload")),
            dispatch=_dispatch_from_json(spec.get("dispatch")),
            drift=_drift_from_json(spec.get("drift")),
            user_block=(None if spec.get("user_block") is None
                        else int(spec["user_block"])),
            cloud=CloudTier.from_json(spec.get("cloud")),
            faults=FaultSchedule.from_json(spec.get("faults")),
            mesh=spec.get("mesh"),
        )

    def canonical_json(self) -> str:
        return json.dumps(self.to_json(), sort_keys=True,
                          separators=(",", ":"))

    @property
    def hash(self) -> str:
        """Stable 16-hex-digit fingerprint of the canonical spec, MINUS
        the mesh: the mesh is execution topology, not scientific
        identity — sharded results are bit-identical to single-device,
        so a ``--sharded`` benchmark artifact must still be gateable
        against the single-device baseline."""
        spec = self.to_json()
        spec.pop("mesh", None)
        return hashlib.sha256(
            json.dumps(spec, sort_keys=True,
                       separators=(",", ":")).encode()).hexdigest()[:16]

    def __eq__(self, other):
        if not isinstance(other, Scenario):
            return NotImplemented
        try:
            return self.to_json() == other.to_json()
        except TypeError:        # unserializable custom component
            return self is other

    def __hash__(self):
        try:
            return hash(self.canonical_json())
        except TypeError:
            return id(self)


# ------------------------------------------- component (de)serializers --

def _tolist(a) -> list:
    return np.asarray(a).tolist()


def _profile_to_json(p):
    if isinstance(p, str):
        return p
    d = {"kind": "inline", "T": _tolist(p.T), "E": _tolist(p.E),
         "mAP": _tolist(p.mAP), "names": list(p.names)}
    d["floor_mw"] = None if p.floor_mw is None else _tolist(p.floor_mw)
    return d


def _profile_from_json(o):
    if isinstance(o, str):
        return o
    return ProfileTable(
        jnp.asarray(o["T"], jnp.float32), jnp.asarray(o["E"], jnp.float32),
        jnp.asarray(o["mAP"], jnp.float32), tuple(o.get("names", ())),
        None if o.get("floor_mw") is None
        else jnp.asarray(o["floor_mw"], jnp.float32))


def _workload_to_json(w):
    # an explicit MarkovWorkload() IS the default: canonicalize to None
    # so default-equivalent scenarios share one spec, hash and equality
    # (the benchmark gate must not refuse {"kind": "markov"} vs null)
    if w is None or isinstance(w, MarkovWorkload):
        return None
    # late import: repro.data.traces imports repro.core.workload
    from repro.data.traces import TraceWorkload
    if isinstance(w, TraceWorkload):
        return {"kind": "trace", "name": w.name,
                "counts": _tolist(w.counts)}
    raise TypeError(f"cannot serialize workload source {type(w).__name__}"
                    " (only the Markov default and TraceWorkload have a "
                    "spec form)")


def _workload_from_json(o):
    if o is None:
        return None
    if o["kind"] == "markov":
        return MarkovWorkload()
    if o["kind"] == "trace":
        from repro.data.traces import TraceWorkload
        return TraceWorkload(np.asarray(o["counts"], np.int32),
                             name=o.get("name", "trace"))
    raise ValueError(f"unknown workload kind {o['kind']!r}")


def _dispatch_to_json(d):
    # an explicit StaticDispatch() IS the default: canonicalize to None
    # (same reasoning as _workload_to_json; from_json still accepts the
    # {"kind": "static"} form in hand-written specs)
    if d is None or isinstance(d, StaticDispatch):
        return None
    if isinstance(d, OnlineDispatch):
        return {"kind": "online", "alpha": d.alpha,
                "prior_weight": d.prior_weight, "window": d.window}
    raise TypeError(f"cannot serialize dispatch engine {type(d).__name__}")


def _dispatch_from_json(o):
    if o is None:
        return None
    if o["kind"] == "static":
        return StaticDispatch()
    if o["kind"] == "online":
        w = o.get("window")
        return OnlineDispatch(alpha=float(o.get("alpha", 0.1)),
                              prior_weight=float(o.get("prior_weight",
                                                       10.0)),
                              window=None if w is None else int(w))
    raise ValueError(f"unknown dispatch kind {o['kind']!r}")


def _drift_to_json(d):
    if d is None:
        return None
    return {"start_step": _tolist(d.start_step),
            "t_scale": _tolist(d.t_scale), "e_scale": _tolist(d.e_scale)}


def _drift_from_json(o):
    if o is None:
        return None
    return DriftSchedule(np.asarray(o["start_step"], np.int32),
                         np.asarray(o["t_scale"], np.float32),
                         np.asarray(o["e_scale"], np.float32))


def _resolve_mesh(spec):
    if spec is None:
        return None
    from jax.sharding import Mesh
    if isinstance(spec, Mesh):
        return spec
    from repro.launch.mesh import make_sweep_mesh
    if spec == "local":
        return make_sweep_mesh()
    return make_sweep_mesh(int(spec))


# --------------------------------------------------------------- Sweep --

class Sweep:
    """Sweep axes by Scenario field name, e.g. ``Sweep(policy=("MO",
    "LT"), stickiness=(0.5, 0.85), seed=range(3))``.

    Any field in :data:`CONFIG_AXES`, :data:`STATIC_AXES` or
    :data:`COMPONENT_AXES` is sweepable; declaration order is the axis
    order of the :class:`Results`. A scalar value counts as a length-1
    axis. The Cartesian product over config-leaf axes runs as ONE fused
    device program (the legacy ``SWEEP_AXES`` grid is the special case
    ``Sweep(policy=..., n_users=..., gamma=..., delta=...,
    oracle_estimator=..., seed=...)``).
    """

    __slots__ = ("axes",)

    def __init__(self, **axes):
        packed = []
        for name, vals in axes.items():
            if name not in _SWEEPABLE:
                raise ValueError(
                    f"unknown sweep axis {name!r}; sweepable Scenario "
                    f"fields: {', '.join(_SWEEPABLE)}")
            if isinstance(vals, (str, bytes)) \
                    or not hasattr(vals, "__iter__"):
                vals = (vals,)
            vals = tuple(vals)
            if not vals:
                raise ValueError(f"sweep axis {name!r} has no values")
            packed.append((name, vals))
        object.__setattr__(self, "axes", tuple(packed))

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(n for n, _ in self.axes)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(v) for _, v in self.axes)

    def values(self, name: str) -> tuple:
        for n, v in self.axes:
            if n == name:
                return v
        raise KeyError(name)

    def __repr__(self):
        inner = ", ".join(f"{n}={len(v)} values" for n, v in self.axes)
        return f"Sweep({inner})"

    def __eq__(self, other):
        return isinstance(other, Sweep) and self.axes == other.axes

    def __hash__(self):
        return hash(("Sweep", tuple((n, len(v)) for n, v in self.axes)))


def _coord_eq(a, b) -> bool:
    """Coordinate equality for Results.sel: identity, then plain ``==``,
    then structural pytree comparison — so a component rebuilt with the
    same values (a round-tripped DriftSchedule, an equal TraceWorkload)
    still selects its axis entry even when its own ``__eq__`` compares
    arrays and cannot produce a bool."""
    if a is b:
        return True
    if isinstance(a, (np.ndarray, jax.Array)) \
            or isinstance(b, (np.ndarray, jax.Array)):
        return bool(np.array_equal(np.asarray(a), np.asarray(b)))
    try:
        return bool(a == b)
    except Exception:              # array-valued component __eq__
        pass
    if type(a) is not type(b):
        return False
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ------------------------------------------------------------- Results --

@dataclass(frozen=True, eq=False)
class Results:
    """Named-axis sweep summaries.

    ``metrics[name]`` is a float64 ndarray whose dimensions follow
    :attr:`axes` (the sweep's declared order, with a leading ``fleet``
    axis when the scenario's profile is a stacked ensemble);
    ``coords[axis]`` holds the coordinate values along each axis.
    :meth:`sel` indexes by coordinate value, so callers never translate
    positions by hand.
    """

    axes: tuple[str, ...]
    coords: dict[str, tuple]
    metrics: dict[str, np.ndarray]
    scenario: Scenario
    sweep: Sweep | None = None

    @property
    def metric_names(self) -> tuple[str, ...]:
        return tuple(self.metrics)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(self.coords[a]) for a in self.axes)

    def __getitem__(self, metric: str) -> np.ndarray:
        return self.metrics[metric]

    def _index_of(self, axis: str, value) -> int:
        for i, v in enumerate(self.coords[axis]):
            if _coord_eq(v, value):
                return i
        raise KeyError(f"{value!r} not on axis {axis!r}; coords: "
                       f"{self.coords[axis]!r}")

    def sel(self, metric: str, **fixed) -> np.ndarray:
        """Select by coordinate value: ``res.sel("latency_ms",
        policy="MO", n_users=15)`` fixes those axes and returns the
        remaining array (a scalar ndarray when everything is fixed)."""
        arr = self.metrics[metric]
        idx: list = [slice(None)] * arr.ndim
        for name, value in fixed.items():
            if name not in self.axes:
                raise KeyError(f"no axis {name!r}; axes: {self.axes}")
            idx[self.axes.index(name)] = self._index_of(name, value)
        return arr[tuple(idx)]

    def mean(self, metric: str, over: str | Sequence[str] = "seed"):
        """Average a metric over one or more named axes (default: the
        ``seed`` axis — the paper's repetition mean)."""
        names = (over,) if isinstance(over, str) else tuple(over)
        dims = tuple(self.axes.index(n) for n in names)
        return self.metrics[metric].mean(axis=dims)

    def scalar(self, metric: str) -> float:
        """The metric as a python float (0-d results only)."""
        arr = self.metrics[metric]
        if arr.ndim:
            raise ValueError(f"{metric} has axes {self.axes}; use sel()")
        return float(arr)

    def __repr__(self):
        ax = ", ".join(f"{a}={len(self.coords[a])}" for a in self.axes)
        return (f"Results([{ax}], metrics={list(self.metrics)}, "
                f"scenario={self.scenario.hash})")


# ------------------------------------------------------------ engine ----

def _stack_drifts(values) -> DriftSchedule | None:
    """Stack same-shape DriftSchedules into one pytree with a leading
    axis (the fused drift-axis form), or None when they don't stack
    (mixed None / differing segment counts -> outer loop instead)."""
    if not all(isinstance(v, DriftSchedule) for v in values):
        return None
    shapes = {tuple(leaf.shape for leaf in jax.tree_util.tree_leaves(v))
              for v in values}
    if len(shapes) > 1:
        return None
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *values)


@functools.partial(jax.jit, static_argnames=("n_requests", "warmup"))
def _drift_axis_fused(prof, workload, dispatch, drifts, cloud, faults,
                      grid, *, n_requests: int, warmup: int):
    """The fused drift axis: vmap the simulate+summarize composition over
    a stacked DriftSchedule — the whole drift × config grid (× fleet) is
    ONE device program, leaves shaped (D, [F,] B)."""

    def one(dr):
        return SIM._fused_summaries(prof, workload, dispatch, dr, cloud,
                                    faults, grid, n_requests=n_requests,
                                    warmup=warmup)

    return jax.vmap(one)(drifts)


def _resolve_axis_profile(value) -> ProfileTable:
    if isinstance(value, str):
        if value not in PROFILE_REGISTRY:
            raise ValueError(f"unknown profile {value!r} on sweep axis")
        return PROFILE_REGISTRY[value]()
    if isinstance(value, ProfileTable):
        return value
    raise TypeError(f"profile axis values must be ProfileTables or "
                    f"registry names, got {type(value)}")


def run(scenario: Scenario, sweep: Sweep | None = None, *,
        mesh=None) -> Results:
    """Evaluate a scenario (optionally swept) and return named-axis
    summaries.

    Axis fusion: config-leaf axes (:data:`CONFIG_AXES`) flatten into the
    batched engine's config axis — one ``vmap(simulate + summarize)``
    under one jit, sharded over the scenario's mesh when set. A ``drift``
    axis over same-shape schedules becomes an extra vmapped batch axis in
    the same program (single-device path); a ``profile`` axis over
    same-shape fleets becomes a stacked fleet axis. Axes over
    ``workload`` / ``dispatch`` / ``n_requests`` / ``warmup_frac`` (and
    non-stackable drift/profile values) run one fused program per value.

    ``mesh`` overrides the scenario's mesh spec and may be an actual
    ``jax.sharding.Mesh`` (the legacy ``sweep_grid(mesh=...)`` shim uses
    this).

    Returns a :class:`Results`; with no sweep the metric arrays are 0-d
    (``Results.scalar``). A stacked-profile scenario adds a leading
    ``fleet`` axis.

    While a profile is being captured the call is one
    ``repro.scenario.run`` span; per outer combination it holds
    ``build_grid``, ``launch``, ``fold`` (user-blocked grids) and
    ``fetch`` spans (:mod:`repro.common.spans`, ``docs/sweep_engine.md``).
    """
    with spans.span("repro.scenario.run"):
        return _run(scenario, sweep, mesh)


def _run(scenario: Scenario, sweep: Sweep | None, mesh) -> Results:
    sweep = sweep if sweep is not None else Sweep()
    mesh_obj = _resolve_mesh(scenario.mesh if mesh is None else mesh)

    config_axes = [(n, v) for n, v in sweep.axes if n in CONFIG_AXES]
    config_names = [n for n, _ in config_axes]
    config_dims = [len(v) for _, v in config_axes]

    profile_axis = None       # ("profile", values) fused via stacking
    drift_axis = None         # ("drift", values, stacked) fused via vmap
    outer_axes: list[tuple[str, tuple]] = []
    for n, v in sweep.axes:
        if n in CONFIG_AXES:
            continue
        if n == "profile":
            tables = [_resolve_axis_profile(x) for x in v]
            if any(t.is_stacked for t in tables):
                raise ValueError("profile axis values must be single "
                                 "(P, G) tables — the axis itself is "
                                 "the ensemble dimension")
            if len({t.T.shape for t in tables}) == 1:
                from repro.core.profiles import stack_profiles
                profile_axis = (n, v, stack_profiles(tables))
                continue
            outer_axes.append((n, tuple(tables)))
        elif n == "drift" and mesh_obj is None \
                and (stacked := _stack_drifts(v)) is not None:
            drift_axis = (n, v, stacked)
        else:
            outer_axes.append((n, v))

    base_prof = profile_axis[2] if profile_axis \
        else scenario.resolve_profile()
    # ANY profile axis (fused or ragged/outer) replaces the scenario's
    # own profile, so the implicit fleet axis only exists when the
    # scenario's stacked profile is actually the one running
    profile_is_outer = any(n == "profile" for n, _ in outer_axes)
    implicit_fleet = profile_axis is None and not profile_is_outer \
        and base_prof.is_stacked

    outer_names = [n for n, _ in outer_axes]
    outer_dims = [len(v) for _, v in outer_axes]

    # a cloud axis mixing None (edge-only) and tiers must still produce
    # one consistent metric set: edge-only combos report offload_share 0
    cloud_vals = next((v for n, v in outer_axes if n == "cloud"),
                      (scenario.cloud,))
    any_cloud = any(v is not None for v in cloud_vals)
    # same rule for a faults axis mixing None and schedules: fault-free
    # combos report zero failed/SLO shares (p99 backfilled below)
    fault_vals = next((v for n, v in outer_axes if n == "faults"),
                      (scenario.faults,))
    any_faults = any(v is not None for v in fault_vals)

    metrics: dict[str, np.ndarray] | None = None
    block_shape: tuple[int, ...] = ()
    for oi, combo in enumerate(itertools.product(
            *(v for _, v in outer_axes))):
        override = dict(zip(outer_names, combo))
        prof = override.pop("profile", base_prof)
        sc = replace(scenario, **{k: v for k, v in override.items()
                                  if k != "drift"}) \
            if any(k != "drift" for k in override) else scenario
        drift = override["drift"] if "drift" in override else sc.drift
        workload = sc.resolve_workload()
        dispatch = sc.resolve_dispatch()
        if sc.cloud is not None:
            if prof.is_stacked:
                raise ValueError("cloud tier does not compose with "
                                 "stacked fleet profiles (each fleet "
                                 "would need its own extension); sweep "
                                 "single-fleet profiles instead")
            prof, cloud_meta = sc.cloud.extend(prof)
        else:
            cloud_meta = None
        fault_meta = sc.resolve_faults(prof.n_pairs)
        n_requests = sc.n_requests
        warmup = int(n_requests * sc.warmup_frac)

        with spans.span("repro.scenario.build_grid"):
            base = dict(n_users=sc.n_users, n_requests=n_requests,
                        policy=sc.policy, gamma=sc.gamma, delta=sc.delta,
                        stickiness=sc.stickiness, seed=sc.seed,
                        warmup_frac=sc.warmup_frac,
                        oracle_estimator=sc.oracle_estimator)
            cfgs = [SIM.SimConfig(**{**base,
                                     **dict(zip(config_names, vals))})
                    for vals in itertools.product(
                        *(v for _, v in config_axes))]
            if sc.user_block is None:
                grid, segments = SIM._make_grid(prof, cfgs,
                                                workload=workload), None
            else:
                # user-blocked grid: each config's balancer-replica
                # blocks are extra rows on the config axis (vmapped/
                # sharded as usual), segment-reduced back to per-config
                # metrics below
                grid, segments = SIM._make_user_grid(prof, cfgs,
                                                     sc.user_block,
                                                     workload=workload)

        with spans.span("repro.scenario.launch"):
            if drift_axis is not None:
                out = _drift_axis_fused(prof, workload, dispatch,
                                        drift_axis[2], cloud_meta,
                                        fault_meta, grid,
                                        n_requests=n_requests,
                                        warmup=warmup)
            else:
                with_hist = segments is not None \
                    and int(np.asarray(segments).shape[0]) > len(cfgs)
                out = SIM._sweep_summaries(prof, workload, dispatch, drift,
                                           cloud_meta, fault_meta, grid,
                                           n_requests=n_requests,
                                           warmup=warmup, mesh=mesh_obj,
                                           with_hist=with_hist)
        if segments is not None:
            with spans.span("repro.scenario.fold"):
                out = SIM.aggregate_block_summaries(out, segments,
                                                    len(cfgs),
                                                    block_axis=-1)

        with spans.span("repro.scenario.fetch"):
            if any_cloud and "offload_share" not in out:
                out = dict(out)
                out["offload_share"] = jnp.zeros_like(out["latency_ms"])
            if any_faults and "slo_violation_share" not in out:
                out = dict(out)
                for m in ("slo_violation_share", "failed_share",
                          "latency_p99_ms"):
                    out[m] = jnp.zeros_like(out["latency_ms"])

            block_shape = ((len(drift_axis[1]),) if drift_axis else ()) \
                + ((prof.n_fleets,) if prof.is_stacked else ()) \
                + tuple(config_dims)
            if metrics is None:
                metrics = {k: np.empty(tuple(outer_dims) + block_shape,
                                       np.float64) for k in out}
            oidx = np.unravel_index(oi, tuple(outer_dims)) if outer_axes \
                else ()
            for k, v in out.items():
                metrics[k][oidx] = np.asarray(
                    v, np.float64).reshape(block_shape)

    with spans.span("repro.scenario.fetch"):
        # internal layout -> declared axis order
        fleet_name = ("profile" if profile_axis
                      else ("fleet" if implicit_fleet else None))
        internal = list(outer_names) \
            + (["drift"] if drift_axis else []) \
            + ([fleet_name] if fleet_name else []) \
            + config_names
        final = (["fleet"] if implicit_fleet else []) + list(sweep.names)
        perm = [internal.index(n) for n in final]
        assert metrics is not None
        # (np.ascontiguousarray would promote 0-d results to 1-d; copy() keeps
        # the transposed layout materialized without changing rank)
        metrics = {k: np.transpose(v, perm).copy() for k, v in metrics.items()}

        coords: dict[str, tuple] = {}
        if implicit_fleet:
            coords["fleet"] = tuple(range(base_prof.n_fleets))
        for n, v in sweep.axes:
            coords[n] = v
        return Results(axes=tuple(final), coords=coords, metrics=metrics,
                       scenario=scenario, sweep=sweep)


def records(scenario: Scenario, sweep: Sweep | None = None):
    """Per-request record arrays for a scenario (the scenario-path
    ``simulate``).

    Without a sweep: a dict of ``(n_requests,)`` arrays for the single
    config (single-fleet profiles only — stacked ensembles need the
    batched form). With a sweep over config-leaf axes only
    (:data:`CONFIG_AXES`): one fused batched run whose record arrays
    carry the named axes as leading dims, shape ``(*axis_lens,
    n_requests)`` (``(F, *axis_lens, n_requests)`` stacked). Rows are
    bit-identical to each config's own single run — the engine's padding
    /batching guarantee.
    """
    prof, cloud_meta = scenario.resolve_fleet()
    workload = scenario.resolve_workload()
    dispatch = scenario.resolve_dispatch()
    if scenario.user_block is not None:
        # single-block configs run the identical program, so records are
        # well-defined (and bit-identical to user_block=None); multi-
        # block configs have no single per-request stream to return
        max_users = max([scenario.n_users]
                        + [max(v) for n, v in (sweep.axes if sweep else ())
                           if n == "n_users"])
        if max_users > scenario.user_block:
            raise ValueError(
                "records() needs n_users <= user_block (a multi-block "
                "config is K independent balancer replicas with no "
                "single record stream); use run() for aggregate metrics")
    fault_meta = scenario.resolve_faults(prof.n_pairs)
    if sweep is None or not sweep.axes:
        return SIM._simulate(prof, scenario.to_config(),
                             workload=workload, dispatch=dispatch,
                             drift=scenario.drift, cloud=cloud_meta,
                             faults=fault_meta)
    bad = [n for n in sweep.names if n not in CONFIG_AXES]
    if bad:
        raise ValueError(
            f"records() sweeps config-leaf axes only {CONFIG_AXES}; "
            f"got {bad} (use run() for component/static axes)")
    base = dict(n_users=scenario.n_users, n_requests=scenario.n_requests,
                policy=scenario.policy, gamma=scenario.gamma,
                delta=scenario.delta, stickiness=scenario.stickiness,
                seed=scenario.seed, warmup_frac=scenario.warmup_frac,
                oracle_estimator=scenario.oracle_estimator)
    names = list(sweep.names)
    cfgs = [SIM.SimConfig(**{**base, **dict(zip(names, vals))})
            for vals in itertools.product(*(v for _, v in sweep.axes))]
    grid = SIM._make_grid(prof, cfgs, workload=workload)
    recs = SIM._simulate_batch(prof, grid,
                               n_requests=scenario.n_requests,
                               workload=workload, dispatch=dispatch,
                               drift=scenario.drift, cloud=cloud_meta,
                               faults=fault_meta)
    dims = sweep.shape
    pre = (prof.n_fleets,) if prof.is_stacked else ()
    return {k: v.reshape(pre + dims + v.shape[len(pre) + 1:])
            for k, v in recs.items()}
