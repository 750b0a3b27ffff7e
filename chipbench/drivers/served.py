"""Traffic kind ``served``: the serving plane, run flat out.

Set-up builds the configuration's fleet on the device, a ``Scenario``
over it and ``ServingPlane.build`` from that, warms the estimator
scatter up at every poll size the mix names, wraps the methods through
which the plane reaches each layer, and warms up with whole calls of
``ServingPlane.run``. The measured window repeats such calls back to
back until ``--seconds`` have passed. Each call admits, routes, submits
and accounts ``windows_per_call`` admission windows of ``window``
requests on the plane's own simulated clock, with the plane's
round-robin stream draw and Markov scenes. The traffic mix gives the
stream count and the offered load, as a share of the fleet's summed
capacity; the fleet comes from the configuration, everything random
from the seed.

A configuration may carry a fault schedule, ``"faults"``, in the
program's ``FaultSchedule`` JSON form: pairs go down and come back each
``epoch`` requests, are throttled, and the plane kills, retries and at
last drops the work they held. Its draws are keyed on a seed made from
``--seed``; the file may not set one. A schedule the replay cannot
check (a router blind to it, WAN jitter with no cloud tier) is refused.

End-to-end metric: ``plane_req_per_s``, the requests of the window's
calls over the window's wall time.

``correct``: the reference replays the whole call log from the plane's
build. It compares the estimated group of every request and the pool's
accounting, and scores every decision routed in the measured window;
under faults it also replays the pool (its throttled service times,
completions, kills and retries) and checks each against the schedule.
"""

from __future__ import annotations

import time

import ml_dtypes
import numpy as np

from chipbench import fleets
from chipbench.reference.serving import PlaneReplay

WRAPPED = {"pool": ("poll", "submit_window"),
           "gateway": ("observe_detections_window", "observe_window",
                       "route_window")}
#: wrapped as well where the configuration carries faults
WRAPPED_FAULTS = ("fail_pairs", "set_fault_throttle")
#: the fault schedule's keys the replay checks
FAULT_KEYS = {"down_rate", "epoch", "throttle_rate", "throttle_t_mult",
              "throttle_e_mult", "timeout_ms", "max_attempts", "outages",
              "visible"}
#: keys of the program's schedule that a configuration may not set
REFUSED = {"seed": "the fault seed is made from --seed",
           "rtt_jitter_ms": "WAN jitter needs a cloud tier",
           "bw_jitter": "WAN jitter needs a cloud tier"}
#: keeps the fault draws apart from the plane's own, which use the seed
FAULT_SALT = 0x5EED_FA17

#: the benchmark's CPU tests run this kind on a small mix: this traffic
#: file, with these keys set
TINY_MIX = {"traffic": "served_paper_static",
            "set": {"windows_per_call": 8, "warmup_calls": 1}}


def program_seed(seed: int) -> int:
    """The program takes 31-bit seeds."""
    return int(seed) % (2 ** 31)


def fault_spec(config: dict) -> dict | None:
    """The configuration's fault schedule, or ``None``; raises on one
    the replay cannot check."""
    spec = config.get("faults")
    if spec is None:
        return None
    for key in sorted(set(spec) - FAULT_KEYS):
        raise ValueError(f"fault schedule key {key!r} refused: "
                         f"{REFUSED.get(key, 'unknown key')}")
    if spec.get("visible", True) is not True:
        raise ValueError("fault schedule key 'visible' refused: a router "
                         "blind to the faults is not checked")
    return spec


class Served:
    def __init__(self, config, traffic, seed, rec):
        from repro.core.dispatch import OnlineDispatch
        from repro.core.faults import FaultSchedule
        from repro.core.scenario import Scenario
        from repro.serving import ServingPlane

        self.traffic, self.rec = traffic, rec
        self.tables = fleets.tables(config)
        self.faults = fault_spec(config)
        self.fault_seed = program_seed(int(seed) + FAULT_SALT)
        online = traffic["dispatch"] == "online"
        sc = Scenario(profile=fleets.profile_table(self.tables),
                      policy=traffic["policy"],
                      n_users=int(traffic["n_streams"]),
                      gamma=float(traffic["gamma"]),
                      delta=float(traffic["delta"]),
                      stickiness=float(traffic["stickiness"]),
                      seed=program_seed(seed),
                      dispatch=OnlineDispatch(
                          alpha=float(traffic["alpha"]),
                          prior_weight=float(traffic["prior_weight"]))
                      if online else None,
                      faults=None if self.faults is None
                      else FaultSchedule.from_json(
                          {**self.faults, "seed": self.fault_seed}))
        self.plane = ServingPlane.build(sc, window=int(traffic["window"]))
        self.plane.offered_rps = float(traffic["load"]) \
            * self.plane.capacity_rps()
        # the estimator scatter compiles once per poll size: warm each
        # size up to ``warmup_poll_sizes`` by writing the fresh state's
        # zeros back, which leaves the state as it was
        gw = self.plane.gateway
        for k in range(1, int(traffic["warmup_poll_sizes"]) + 1):
            gw.observe_detections_window(np.arange(k) % gw.n_streams,
                                         np.zeros(k, np.int64))
        rec.wrap(self.plane.pool, "pool", WRAPPED["pool"] + (
            () if self.faults is None else WRAPPED_FAULTS))
        rec.wrap(self.plane.gateway, "gateway", WRAPPED["gateway"])
        self.per_call = int(traffic["window"]) * int(
            traffic["windows_per_call"])
        for _ in range(int(traffic["warmup_calls"])):
            self.plane.run(n_requests=self.per_call)
        self.log_start = len(rec.log)

    def window(self, seconds: float) -> dict:
        calls = windows = 0
        self.dropped0 = self.dropped()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with self.rec.span("plane.run"):
                recs = self.plane.run(n_requests=self.per_call)
            calls += 1
            windows += len(recs["router_window_s"])
        self.wall = time.perf_counter() - t0
        self.requests = calls * self.per_call
        self.windows = windows
        return {"plane_req_per_s": self.requests / self.wall}

    def counts(self) -> dict:
        """What the per-layer metrics divide by."""
        return {"windows": self.windows, "decisions": self.requests,
                "window": int(self.traffic["window"]),
                "n_groups": int(self.tables["T"].shape[1]),
                "n_pairs": int(self.tables["T"].shape[0])}

    def attempted(self) -> tuple[int, int]:
        """The window's requests, and those of them the plane gave up
        on past ``max_attempts`` (a killed try that is retried is not a
        failure; without faults nothing is killed)."""
        return self.requests, self.dropped() - self.dropped0

    def dropped(self) -> int:
        """Requests the plane has dropped past ``max_attempts``."""
        return getattr(self.plane, "failed_requests", 0)

    def controls(self) -> tuple:
        """The controls :meth:`check` can put in the program's place."""
        return ("bfloat16",) if self.faults is None \
            else ("bfloat16", "blind")

    def check(self, control=None) -> dict:
        """Numbers compared with the reference: the widest decision gap,
        the estimated groups that differ, the pool's accounting and,
        under faults, the kills and retries, and the throttled service
        and completions, that differ from the schedule's. ``control``,
        one of :meth:`controls`, puts a control in the routing kernel's
        place: the reference's bfloat16 scoring (``"bfloat16"``) or,
        under faults, its pick blind to the health row (``"blind"``)."""
        score_dtype = ml_dtypes.bfloat16 if control == "bfloat16" else None
        tr = self.traffic
        online = {"alpha": tr["alpha"], "prior_weight": tr["prior_weight"]} \
            if tr["dispatch"] == "online" else None
        gw = self.plane.gateway
        ref = PlaneReplay(self.tables["T"], self.tables["E"],
                          self.tables["mAP"], n_streams=gw.n_streams,
                          delta=tr["delta"], gamma=tr["gamma"],
                          online=online, faults=self.faults,
                          fault_seed=self.fault_seed)
        faults = self.faults is not None
        gap, mismatched, completed = 0.0, 0, 0
        for i, (name, args, kwargs, out) in enumerate(self.rec.log):
            if name == "gateway.observe_detections_window":
                ref.detections(*args)
                completed += len(args[0])
            elif name == "gateway.observe_window":
                ref.observations(*args, **kwargs)
            elif name == "gateway.route_window":
                ids, q0 = args
                pairs, gs, _q = out
                want = ref.groups(ids)
                mismatched += int(np.sum(want != np.asarray(gs)))
                g = ref.route(want, q0, np.asarray(pairs),
                              score=i >= self.log_start,
                              score_dtype=score_dtype,
                              blind=control == "blind")
                if g is not None:
                    gap = max(gap, float(g.max()))
            elif not faults:
                continue
            elif name == "pool.submit_window":
                ref.submitted(out.rids, out.stream_ids, out.groups,
                              out.pairs, out.arrival_s, out.finish_s,
                              out.energy_mwh)
            elif name == "pool.poll":
                ref.polled(args[0], out.rids)
            elif name == "pool.fail_pairs":
                ref.killed(args[1], out.rids)
            elif name == "pool.set_fault_throttle":
                ref.throttled(*args)
        pool = self.plane.pool
        unbalanced = abs(pool.submitted - pool.polled - pool.failed
                         - pool.in_flight) \
            + abs(pool.submitted - completed - pool.failed)
        checks = {"decision_gap": gap, "group_mismatch": mismatched,
                  "pool_unbalanced": unbalanced}
        if faults:
            checks["retry_mismatch"] = ref.retry_mismatch(self.dropped())
            checks["service_mismatch"] = ref.service_mismatch()
        return checks


def setup(config, traffic, seed, rec):
    return Served(config, traffic, seed, rec)
