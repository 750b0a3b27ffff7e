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

End-to-end metric: ``plane_req_per_s``, the requests of the window's
calls over the window's wall time.

``correct``: the reference replays the whole call log from the plane's
build. It compares the estimated group of every request and the pool's
accounting, and scores every decision routed in the measured window.
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


def program_seed(seed: int) -> int:
    """The program takes 31-bit seeds."""
    return int(seed) % (2 ** 31)


class Served:
    def __init__(self, config, traffic, seed, rec):
        from repro.core.dispatch import OnlineDispatch
        from repro.core.scenario import Scenario
        from repro.serving import ServingPlane

        self.traffic, self.rec = traffic, rec
        self.tables = fleets.tables(config)
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
                      if online else None)
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
        rec.wrap(self.plane.pool, "pool", WRAPPED["pool"])
        rec.wrap(self.plane.gateway, "gateway", WRAPPED["gateway"])
        self.per_call = int(traffic["window"]) * int(
            traffic["windows_per_call"])
        for _ in range(int(traffic["warmup_calls"])):
            self.plane.run(n_requests=self.per_call)
        self.log_start = len(rec.log)

    def window(self, seconds: float) -> dict:
        calls = windows = 0
        self.failed0 = self.plane.pool.failed
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
        return self.requests, self.plane.pool.failed - self.failed0

    def check(self, control: bool = False) -> dict:
        """Numbers compared with the reference: the widest decision gap,
        the estimated groups that differ, and the pool's accounting.
        ``control`` puts the reference's bfloat16 scoring in the
        routing kernel's place (the control of the decision gap)."""
        score_dtype = ml_dtypes.bfloat16 if control else None
        tr = self.traffic
        online = {"alpha": tr["alpha"], "prior_weight": tr["prior_weight"]} \
            if tr["dispatch"] == "online" else None
        gw = self.plane.gateway
        ref = PlaneReplay(self.tables["T"], self.tables["E"],
                          self.tables["mAP"], n_streams=gw.n_streams,
                          delta=tr["delta"], gamma=tr["gamma"],
                          online=online)
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
                if i >= self.log_start:
                    g = ref.gaps(want, q0, np.asarray(pairs),
                                 score_dtype=score_dtype)
                    gap = max(gap, float(g.max()))
        pool = self.plane.pool
        unbalanced = abs(pool.submitted - pool.polled - pool.failed
                         - pool.in_flight) + abs(pool.submitted - completed)
        return {"decision_gap": gap, "group_mismatch": mismatched,
                "pool_unbalanced": unbalanced}


def setup(config, traffic, seed, rec):
    return Served(config, traffic, seed, rec)
