"""Traffic kind ``served``: the serving plane, run flat out.

Set-up builds the configuration's fleet on the device, a ``Scenario``
over it and ``ServingPlane.build`` from that, wraps the methods through
which the plane reaches each layer, and warms up with whole calls of
``ServingPlane.run``. The measured window repeats such calls back to
back until ``--seconds`` have passed. Each call admits, routes, submits
and accounts ``windows_per_call`` admission windows of ``window``
requests on the plane's own simulated clock (offered load 0.9 x fleet
capacity, the plane's default), with the plane's round-robin stream
draw and Markov scenes; the stream count and the fleet come from the
configuration, everything random from the seed.

End-to-end metrics: ``plane_req_per_s`` (requests of the window's calls
over the window's wall time) and ``decision_p90_ms`` (90th percentile,
over all requests routed in the window, of the wall time of their
window's ``route_window`` call, which ends when the decisions are on the
host).

``correct``: the reference replays the whole call log from the plane's
build. It compares the estimated group of every request and the pool's
accounting, and scores every decision of ``check_windows`` windows of
the measured window, drawn from the seed.
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
POOL_SPANS = ("pool.poll", "pool.submit_window")
OBSERVE_SPANS = ("gateway.observe_detections_window",
                 "gateway.observe_window")


def program_seed(seed: int) -> int:
    """The program takes 31-bit seeds."""
    return int(seed) % (2 ** 31)


class Served:
    def __init__(self, config, traffic, seed, rec):
        from repro.core.dispatch import OnlineDispatch
        from repro.core.scenario import Scenario
        from repro.serving import ServingPlane

        self.traffic, self.rec, self.seed = traffic, rec, int(seed)
        self.tables = fleets.tables(config)
        online = traffic["dispatch"] == "online"
        sc = Scenario(profile=fleets.profile_table(self.tables),
                      policy=traffic["policy"],
                      n_users=int(config["n_streams"]),
                      gamma=float(traffic["gamma"]),
                      delta=float(traffic["delta"]),
                      stickiness=float(traffic["stickiness"]),
                      seed=program_seed(seed),
                      dispatch=OnlineDispatch(
                          alpha=float(traffic["alpha"]),
                          prior_weight=float(traffic["prior_weight"]))
                      if online else None)
        self.plane = ServingPlane.build(sc, window=int(traffic["window"]))
        rec.wrap(self.plane.pool, "pool", WRAPPED["pool"])
        rec.wrap(self.plane.gateway, "gateway", WRAPPED["gateway"])
        self.per_call = int(traffic["window"]) * int(
            traffic["windows_per_call"])
        for _ in range(int(traffic["warmup_calls"])):
            self.plane.run(n_requests=self.per_call)
        self.log_start = len(rec.log)

    def window(self, seconds: float) -> dict:
        win_s, win_n = [], []
        calls = 0
        self.failed0 = self.plane.pool.failed
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with self.rec.span("plane.run"):
                recs = self.plane.run(n_requests=self.per_call)
            calls += 1
            win_s.append(np.asarray(recs["router_window_s"]))
            win_n.append(np.full(len(recs["router_window_s"]),
                                 int(self.traffic["window"])))
        wall = time.perf_counter() - t0
        self.wall = wall
        self.requests = calls * self.per_call
        self.windows = int(sum(len(w) for w in win_s))
        per_req = np.repeat(np.concatenate(win_s), np.concatenate(win_n))
        return {"plane_req_per_s": self.requests / wall,
                "decision_p90_ms": float(np.percentile(per_req, 90) * 1e3)}

    def counts(self) -> dict:
        """What the per-layer metrics divide by."""
        return {"windows": self.windows, "decisions": self.requests,
                "window": int(self.traffic["window"]),
                "n_groups": int(self.tables["T"].shape[1]),
                "n_pairs": int(self.tables["T"].shape[0]),
                "pool_spans": POOL_SPANS, "observe_spans": OBSERVE_SPANS}

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
        timed = [i for i, ev in enumerate(self.rec.log)
                 if i >= self.log_start and ev[0] == "gateway.route_window"]
        pick = np.random.default_rng(self.seed).permutation(len(timed))
        scored = {timed[k] for k in pick[:int(tr["check_windows"])]}
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
                if i in scored:
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
