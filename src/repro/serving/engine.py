"""End-to-end serving engines: workload -> gateway -> executors -> metrics.

Two drivers share the stack:

* :class:`ServingPlane` — the windowed (micro-batched) request plane.
  Requests are admitted a window at a time; one jitted ``route_window``
  call routes the whole window against the live executor queue depths,
  the :class:`~repro.serving.executor.AsyncExecutorPool` enqueues it
  without blocking, and completions polled between windows feed the
  gateway's windowed observation hooks (dispatch-state belief and the
  detection-count estimator). Built from a
  :class:`~repro.core.scenario.Scenario`; this is the high-throughput
  path (``benchmarks/serving_throughput.py`` drives the same machinery).

* :class:`ServingEngine` — the original per-request closed loop, kept
  for ``real`` mode (actual tiny detectors on this host, wall-clock
  service times, real detection counts feeding the estimator — the full
  loop of the paper's §III). It now drives the SAME windowed gateway
  with windows of one, so it emits no deprecation warnings and stays
  bit-compatible with the windowed plane on a shared request stream.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass, field

import numpy as np

from repro.common import spans
from repro.core import estimator as EST
from repro.core.profiles import ProfileTable
from repro.data.workload import VideoStreamWorkload
from repro.serving.executor import AsyncExecutorPool, Executor
from repro.serving.gateway import WindowedGateway
from repro.serving.request import Request

# detection probability of one object given pair mAP (workload.noisy_count
# and the estimator's noisy_detected_count use the same ramp)
_P_DET = lambda m: np.minimum(1.0, 0.80 + 0.20 * m / 100.0)


@dataclass
class ServingPlane:
    """Windowed closed-ish loop over a modelled fleet.

    Per iteration: poll the pool for completions (feeding the gateway's
    windowed observation hooks), admit the next window of streams,
    route it in one jitted call against live queue depths, enqueue it on
    the pool, and advance simulated time by the window's offered-load
    interval. Scene complexity per stream follows the same Markov chain
    as the simulator/workload.

    Under a scenario :class:`~repro.core.faults.FaultSchedule` the plane
    closes the failover loop: before each window it kills in-flight work
    on pairs the fault plane took down (and work past the schedule's
    ``timeout_ms``) via :meth:`~repro.serving.executor
    .AsyncExecutorPool.fail_pairs`, re-admits the victims at the head of
    later windows (re-routed against the CURRENT health mask) up to
    ``max_attempts`` total tries — beyond that the request is dropped
    and counted in ``failed_share`` — and records a completed retry's
    latency from its FIRST arrival, so retries pay their full
    end-to-end price. Throttling bursts SET the pool's true-time
    multipliers per window (``truth = (prof x drift) x fault``)."""

    gateway: WindowedGateway
    pool: AsyncExecutorPool
    window: int = 64
    n_streams: int = 15
    stickiness: float = 0.85
    offered_rps: float | None = None   # None: ~90% of fleet capacity
    seed: int = 0
    _recs: dict = field(default=None, repr=False)

    @classmethod
    def build(cls, scenario, *, window: int = 64, backend: str = "auto",
              offered_rps: float | None = None) -> "ServingPlane":
        """One Scenario -> the whole plane: the gateway adopts the
        scenario's profile/policy/γ/Δ/dispatch/seed, the pool its fleet,
        the workload its user count and stickiness."""
        gw = WindowedGateway(scenario, backend=backend,
                             n_streams=max(1024, scenario.n_users))
        return cls(gw, AsyncExecutorPool(gw.prof), window=window,
                   n_streams=scenario.n_users,
                   stickiness=scenario.stickiness,
                   offered_rps=offered_rps, seed=scenario.seed)

    def capacity_rps(self) -> float:
        """Aggregate fleet service capacity (completions/sec) at the
        pool's CURRENT true mean service times (post-drift/throttle),
        not the offline profile. The default offered load is 90% of
        this; leave more headroom when faults are in play so failover
        has spare capacity to absorb the re-routed work."""
        return float(np.sum(1.0 / self.pool._T_s.mean(axis=1)))

    def _observe(self, resp, rng) -> None:
        """Feed one polled completion window back: measured latency and
        energy into the dispatch state (keyed by the ESTIMATED group the
        decision used), modelled detection counts into the estimator."""
        if resp.size == 0:
            return
        # belief updates see the per-SUBMISSION latency (the measurement
        # an executor would report); the recorded latency of a retried
        # request runs from its FIRST arrival instead
        self.gateway.observe_window(resp.pairs, resp.est_groups,
                                    resp.latency_ms, resp.energy_mwh)
        true_count = np.where(resp.groups < self.gateway.prof.n_groups - 1,
                              resp.groups, 5)
        det = rng.binomial(true_count, _P_DET(resp.map_proxy))
        det += rng.random(resp.size) < 0.05 * (1 - resp.map_proxy / 100.0)
        self.gateway.observe_detections_window(resp.stream_ids, det)
        lat_s = resp.latency_ms / 1000.0
        if getattr(self, "_first_arrival", None):
            for j, r in enumerate(resp.rids):
                fa = self._first_arrival.pop(int(r), None)
                if fa is not None:
                    lat_s[j] = resp.finish_s[j] - fa
                self._attempts.pop(int(r), None)
        r = self._recs
        r["latency"].append(lat_s)
        r["energy"].append(resp.energy_mwh)
        r["map"].append(resp.map_proxy)
        r["pair"].append(resp.pairs)
        r["g_true"].append(resp.groups)
        r["g_est"].append(resp.est_groups)

    def _requeue(self, failed) -> None:
        """Queue a :meth:`fail_pairs` window for retry: each victim gets
        re-admitted (original rid/stream/true group, so its identity and
        first-arrival clock survive) unless it has exhausted the
        schedule's ``max_attempts`` — then it is dropped for good."""
        cap = int(self.gateway.faults.max_attempts)
        for j in range(failed.size):
            rid = int(failed.rids[j])
            self._first_arrival.setdefault(rid, float(failed.arrival_s[j]))
            n = self._attempts.get(rid, 1) + 1
            if n > cap:
                self._attempts.pop(rid, None)
                self.failed_requests += 1
                continue
            self._attempts[rid] = n
            self.retried += 1
            self._retryq.append((rid, int(failed.stream_ids[j]),
                                 int(failed.groups[j])))

    def run(self, n_requests: int = 2048):
        """Drive ``n_requests`` through the plane; returns per-request
        record arrays (completion order) plus router timing:
        ``router_s`` (total wall-clock inside ``route_window``) and
        ``router_window_s`` (per-window wall-clock samples). Repeated
        calls CONTINUE the plane — clock, streams, queues and belief
        state persist — so drift can be injected between runs.

        While a profile is being captured each admission window is a
        ``repro.plane.window`` span holding ``faults``, ``poll``,
        ``observe``, ``admit``, ``route`` and ``submit`` spans and the
        window's counters (:mod:`repro.common.spans`, ``docs/serving.md``).
        """
        G = self.gateway.prof.n_groups
        meta = self.gateway._fault_meta
        if getattr(self, "_rng", None) is None:     # first run: cold plane
            self._rng = np.random.default_rng(self.seed)
            P_mat = np.asarray(EST.markov_transition(G, self.stickiness))
            self._cumP = P_mat.cumsum(axis=1)
            # a float32 row sum can end below 1 (0.99999994 on a TPU):
            # a uniform above it would step to group G, out of range
            self._cumP[:, -1] = 1.0
            self._scene = self._rng.choice(
                G, self.n_streams, p=np.asarray(EST.stationary(P_mat)))
            self._now = 0.0
            self._served = 0
            self._windows = 0           # admission windows so far
            self._retryq = []           # [(rid, stream, g_true), ...]
            self._attempts = {}         # rid -> submissions so far
            self._first_arrival = {}    # rid -> first arrival_s
            self.failed_requests = 0    # dropped past max_attempts
            self.retried = 0            # re-admissions
        rng, cumP, scene = self._rng, self._cumP, self._scene
        rps = self.offered_rps or 0.9 * self.capacity_rps()
        self._recs = {k: [] for k in ("latency", "energy", "map", "pair",
                                      "g_true", "g_est")}
        router_win = []
        failed0, retried0 = self.failed_requests, self.retried
        timeout_s = None if meta is None \
            else float(self.gateway.faults.timeout_ms) / 1000.0
        now, done = self._now, 0
        while done < n_requests or self._retryq:
            rid0 = self._served + done
            with spans.span("repro.plane.window", window=self._windows,
                            rid0=rid0):
                if meta is not None:
                    with spans.span("repro.plane.faults"):
                        step0 = self.gateway._step
                        if meta.has_down:
                            down = np.asarray(meta.down_at(step0))
                            self._requeue(self.pool.fail_pairs(
                                down, now, timeout_s=timeout_s))
                        if meta.has_throttle:
                            t_m, e_m = meta.throttle_at(step0)
                            self.pool.set_fault_throttle(
                                np.asarray(t_m)[:, None],
                                np.asarray(e_m)[:, None])
                with spans.span("repro.plane.poll"):
                    resp = self.pool.poll(now)
                with spans.span("repro.plane.observe"):
                    self._observe(resp, rng)
                with spans.span("repro.plane.admit"):
                    # queued retries drain at the head of the window
                    # (re-routed against the CURRENT health mask), new
                    # streams fill the rest
                    retry = self._retryq[:self.window]
                    del self._retryq[:len(retry)]
                    w_new = min(self.window - len(retry), n_requests - done)
                    new_streams = np.arange(rid0, rid0 + w_new) \
                        % self.n_streams
                    scene[new_streams] = (rng.random((w_new, 1))
                                          > cumP[scene[new_streams]]
                                          ).sum(axis=1)
                    streams = np.concatenate(
                        [np.asarray([s for _, s, _ in retry], np.int64),
                         new_streams])
                    rids = np.concatenate(
                        [np.asarray([r for r, _, _ in retry], np.int64),
                         np.arange(rid0, rid0 + w_new)])
                    groups = np.concatenate(
                        [np.asarray([g for _, _, g in retry], np.int64),
                         scene[new_streams]])
                with spans.timed("repro.plane.route") as route:
                    pairs, gs, _q = self.gateway.route_window(
                        streams, self.pool.depths())
                    pairs = np.asarray(pairs)
                router_win.append(route.seconds)
                with spans.span("repro.plane.submit"):
                    self.pool.submit_window(pairs, groups, now,
                                            est_groups=np.asarray(gs),
                                            stream_ids=streams, rids=rids)
                if spans.collecting():
                    spans.count("plane.admitted", w_new)
                    spans.count("plane.retried", len(retry))
                    spans.count("pool.in_flight", self.pool.in_flight)
            now += streams.shape[0] / rps
            done += w_new
            self._windows += 1
        self._observe(self.pool.poll(np.inf), rng)   # drain the tail
        self._now = max(now, float(self.pool._avail.max(initial=0.0)))
        self._served += done
        recs = {k: np.concatenate(v) for k, v in self._recs.items()}
        recs["router_s"] = float(np.sum(router_win))
        recs["router_window_s"] = np.asarray(router_win)
        if meta is not None:
            recs["n_offered"] = float(n_requests)
            recs["failed_requests"] = float(self.failed_requests - failed0)
            recs["retried"] = float(self.retried - retried0)
        return recs

    @staticmethod
    def summarize(recs) -> dict:
        """:meth:`ServingEngine.summarize`, extended with the fault
        plane's availability metrics when the run carried them."""
        out = ServingEngine.summarize(recs)
        if "n_offered" in recs:
            n = max(1.0, float(recs["n_offered"]))
            out["failed_share"] = float(recs["failed_requests"]) / n
            out["retried_share"] = float(recs["retried"]) / n
            out["latency_p99_ms"] = float(
                np.percentile(recs["latency"], 99) * 1000)
        return out


@dataclass
class ServingEngine:
    prof: ProfileTable
    gateway: WindowedGateway
    executors: list
    workload: VideoStreamWorkload

    @classmethod
    def build(cls, prof: ProfileTable, *, policy="MO", gamma=0.5, delta=20.0,
              n_streams=8, mode="modelled", tiers=None, online=False,
              dispatch=None, img_res=64, seed=0):
        gw = WindowedGateway(prof, policy=policy, gamma=gamma, delta=delta,
                             online=online, dispatch=dispatch)
        tiers = tiers or ["ssd_v1"] * prof.n_pairs
        exs = [Executor(i, str(prof.names[i] if prof.names else i), prof,
                        mode=mode, tier=tiers[i])
               for i in range(prof.n_pairs)]
        wl = VideoStreamWorkload(n_streams=n_streams, img_res=img_res,
                                 n_groups=prof.n_groups, seed=seed)
        return cls(prof, gw, exs, wl)

    def run(self, n_requests: int = 200, concurrency: int | None = None):
        """Closed-loop: ``concurrency`` streams each keep one request in
        flight (Locust semantics). Returns per-request record arrays.
        Per-request = windows of one on the windowed gateway."""
        conc = concurrency or self.workload.n_streams
        recs = {k: [] for k in ("latency", "energy", "map", "pair", "g_true",
                                "g_est", "q")}
        # event heap of (ready_time, stream)
        heap = [(i * 1e-4, s) for i, s in enumerate(range(conc))]
        heapq.heapify(heap)
        done = 0
        while done < n_requests:
            now, stream = heapq.heappop(heap)
            frame, g_true = self.workload.next_frame(stream)
            req = Request(rid=done, stream_id=stream, arrival_s=now,
                          payload=frame)
            q = np.array([ex.outstanding(now) for ex in self.executors],
                         np.float32)
            ps, gs, _qa = self.gateway.route_window([stream], q)
            pair, g_est = int(ps[0]), int(gs[0])
            resp = self.executors[pair].submit(req, g_true, now)
            if resp.detected_count >= 0:      # real detector output
                self.gateway.observe_detections_window(
                    [stream], [resp.detected_count])
            else:                             # modelled detection count
                det = self.workload.noisy_count(
                    stream, float(self.prof.mAP[pair, g_true]))
                self.gateway.observe_detections_window([stream], [det])
            self.gateway.observe_window([pair], [g_est],
                                        [(resp.finish_s - now) * 1000.0],
                                        [resp.energy_mwh])
            recs["latency"].append(resp.finish_s - now)
            recs["energy"].append(resp.energy_mwh)
            recs["map"].append(resp.map_proxy)
            recs["pair"].append(pair)
            recs["g_true"].append(g_true)
            recs["g_est"].append(g_est)
            recs["q"].append(q[pair])
            heapq.heappush(heap, (resp.finish_s, stream))
            done += 1
        return {k: np.asarray(v) for k, v in recs.items()}

    @staticmethod
    def summarize(recs) -> dict:
        lat = recs["latency"]
        return {
            "latency_ms": float(lat.mean() * 1000),
            "latency_p90_ms": float(np.percentile(lat, 90) * 1000),
            "energy_mwh": float(recs["energy"].mean()),
            "map": float(recs["map"].mean()),
            "estimator_acc": float((recs["g_true"] == recs["g_est"]).mean()),
        }
