"""Traffic kind ``sweep``: what-if grids through ``core.scenario.run``.

Set-up builds the configuration's fleet, a ``Scenario`` over it and a
``Sweep`` of the traffic's axes (policy x users x seeds, where the
seeds are ``--seed`` and the ``seed_offsets`` after it), and runs the
grid once to compile and warm up. The measured window repeats the grid
back to back until ``--seconds`` have passed; each run ends when its
results are on the host.

End-to-end metric: ``sweep_req_per_s``, the requests simulated (summed
over the grid's rows after user-block decomposition, each row's scan
length) over the window's wall time.

``correct``: the plain reference simulates every row of the grid and
computes the same per-config metrics. Two numbers are compared: the
widest relative gap over configs of the 90th latency percentile, and
over configs and every other metric.
"""

from __future__ import annotations

import itertools
import math
import time

import numpy as np

from chipbench import fleets
from chipbench.reference import simulator as ref

#: per-config metrics compared with the reference
METRICS = ("latency_ms", "latency_p90_ms", "throughput_rps", "energy_mwh",
           "map", "estimator_acc", "makespan_s")
#: the benchmark's CPU tests run this kind on a small mix: this traffic
#: file, with these keys set
TINY_MIX = {"traffic": "sweep_fig4",
            "set": {"n_users": [1, 5, 15], "n_requests": 300,
                    "seed_offsets": [0, 1]}}


def program_seeds(seed: int, offsets) -> tuple:
    """The program takes 31-bit seeds; every offset stays in range."""
    base = int(seed) % (2 ** 31 - 1 - max(offsets))
    return tuple(base + int(o) for o in offsets)


class Sweep:
    def __init__(self, config, traffic, seed, rec):
        from repro.core.scenario import Scenario, Sweep as Axes, run

        self.traffic, self.rec = traffic, rec
        self.tables = fleets.tables(config)
        self.seeds = program_seeds(seed, traffic["seed_offsets"])
        self.scenario = Scenario(
            profile=fleets.profile_table(self.tables),
            n_requests=int(traffic["n_requests"]),
            gamma=float(traffic["gamma"]), delta=float(traffic["delta"]),
            stickiness=float(traffic["stickiness"]),
            warmup_frac=float(traffic["warmup_frac"]),
            user_block=traffic.get("user_block"),
            mesh=traffic.get("mesh"))
        self.axes = Axes(policy=tuple(traffic["policies"]),
                         n_users=tuple(traffic["n_users"]),
                         seed=self.seeds)
        self._run = run
        block = traffic.get("user_block")
        self.rows_per_grid = len(traffic["policies"]) * len(self.seeds) * sum(
            1 if block is None else math.ceil(n / block)
            for n in traffic["n_users"])
        self.per_grid = self.rows_per_grid * int(traffic["n_requests"])
        rec.wrap(self, "scenario", ("grid",))
        self.result = self.grid()

    def grid(self):
        res = self._run(self.scenario, self.axes)
        return {k: np.asarray(res[k]) for k in METRICS}

    def window(self, seconds: float) -> dict:
        runs = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.result = self.grid()
            runs += 1
        wall = time.perf_counter() - t0
        self.runs = runs
        return {"sweep_req_per_s": runs * self.per_grid / wall}

    def counts(self) -> dict:
        return {"grid_runs": self.runs}

    def attempted(self) -> tuple[int, int]:
        return self.runs * self.per_grid, 0

    def controls(self) -> tuple:
        """The controls :meth:`check` can put in the program's place."""
        return ("bfloat16",)

    def check(self, control=None) -> dict:
        """The widest relative gap between the last grid's per-config
        metrics and the reference's. ``control`` ``"bfloat16"`` runs the
        reference in bfloat16 in the program's place."""
        import jax.numpy as jnp

        tr = self.traffic
        configs = [{"policy": p, "n_users": n, "seed": s,
                    "gamma": tr["gamma"], "delta": tr["delta"],
                    "stickiness": tr["stickiness"]}
                   for p, n, s in itertools.product(
                       tr["policies"], tr["n_users"], self.seeds)]
        G = self.tables["T"].shape[1]
        rws, seg = ref.rows(configs, G, tr.get("user_block"))
        n_req = int(tr["n_requests"])
        want = ref.summaries(
            ref.simulate(self.tables, rws, n_requests=n_req),
            seg, len(configs), self.tables["floor_mw"],
            warmup=int(n_req * float(tr["warmup_frac"])))
        if control == "bfloat16":
            got = ref.summaries(
                ref.simulate(self.tables, rws, n_requests=n_req,
                             dtype=jnp.bfloat16),
                seg, len(configs), self.tables["floor_mw"],
                warmup=int(n_req * float(tr["warmup_frac"])))
        else:
            got = {k: v.reshape(-1) for k, v in self.result.items()}
        def gap(keys):
            worst = 0.0
            for k in keys:
                w = np.asarray(want[k], np.float64)
                g = np.asarray(got[k], np.float64)
                rel = np.abs(g - w) / np.maximum(np.abs(w), 1e-12)
                worst = max(worst, float(np.max(
                    np.where(np.isfinite(rel), rel, np.inf))))
            return worst

        return {"metric_gap": gap(k for k in METRICS
                                  if k != "latency_p90_ms"),
                "p90_gap": gap(["latency_p90_ms"])}


def setup(config, traffic, seed, rec):
    return Sweep(config, traffic, seed, rec)
