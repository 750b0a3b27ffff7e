"""Plain reference of the serving plane's decisions, in NumPy float64.

It replays the plane's call log in order and keeps its own state:

* the estimator: the last detected object count of every stream, written
  by each observation window with the latest entry of a stream winning;
  a request's scene group is ``clip(count, 0, G - 1)`` (paper §III-B.1:
  groups {0, 1, 2, 3, 4+});
* under online dispatch, the belief tables: per (pair, group) an
  annealed EWMA of observed latency and energy, with step
  ``alpha * c / (c + prior_weight)`` after ``c`` earlier observations of
  the cell, folded in completion order (mAP is never adapted);
* Algorithm 1 (paper §III-B): among the pairs whose mAP for the group is
  within ``delta`` of the best, minimise
  ``gamma * L_n + (1 - gamma) * E_n``, where ``L = T * (1 + q)`` is the
  expected latency at live queue depth ``q`` and ``L_n``, ``E_n`` are
  min-max normalised over the feasible pairs (denominators floored at
  1e-9); decision ``w + 1`` of a window sees the queue bump of decision
  ``w``.

Every decision is scored teacher-forced: at the queue depths the
program's own earlier decisions of the window left. Its gap is the
reference score of the program's pair minus the best reference score
(0 when they agree or tie; infinite for an infeasible pair).

Under a fault schedule (the configuration's ``faults``, with the seed
the benchmark gives the program) the replay also knows which pairs are
down:

* a decision's absolute step is the count of requests routed before it
  since the gateway was built, retries included;
* pair ``p`` is down at step ``s`` when
  ``uniform(fold_in(fold_in(PRNGKey(seed), 0), s // epoch), (P,))[p]``
  (float32) is below ``down_rate``, or a scripted outage
  ``(p, start, end)`` has ``start <= s < end``; the router's health row
  is the complement, relaxed to all pairs when every pair is down;
* Algorithm 1 then scores the pairs that are both accuracy-feasible
  (against the best mAP of the whole fleet) and healthy. When no pair
  is both, the candidates are every healthy pair and the energy term is
  zeroed, so the best is the healthy pair of least expected latency. A
  decision sent to a down pair while a healthy one exists scores
  infinite;
* a pair is throttled at step ``s`` when
  ``uniform(fold_in(fold_in(PRNGKey(seed), 1), s // epoch), (P,))[p]``
  is below ``throttle_rate``: its service times are multiplied by
  ``throttle_t_mult`` and its energies by ``throttle_e_mult`` (float32
  multipliers) for the work submitted in a window that starts there;
* the pool is a FIFO queue per pair: a window submitted at ``now`` on
  pair ``p`` finishes one by one from ``max(now, frontier_p)``, each
  after its service time ``T[p, g] / 1000 * t_mult_p`` in seconds; a
  poll at ``now`` returns exactly the work finished by then;
* where the schedule takes pairs down, each admission window starts with
  the fault phase: the pool kills every submission still unfinished at
  that moment that sits on a pair down at the window's first step or
  would finish later than its arrival plus ``timeout_ms``, and nothing
  else; each touched pair's frontier falls back to the latest finish of
  the work it keeps (0 with none). Each victim is submitted again later
  with the same request id, stream and true group, unless it has
  already been submitted ``max_attempts`` times; then it is dropped,
  and counted.

It imports nothing of the program. ``score_dtype`` computes the scores
in a lower precision to make the control: the pair that precision puts
first, judged by the float64 scores. ``blind`` makes the fault control:
the pair the scores without the health row put first, judged with it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512   # decisions scored per block, to bound the (rows, P) temporaries
EPOCHS = 1024   # outage epochs drawn per device call


def _scores(T, E, feas, gs, q, *, gamma, dtype, health=None):
    """Algorithm-1 scores of a block of decisions: (rows, P). ``health``
    (rows, P) masks down pairs, with the degraded rule of the module
    docstring."""
    T = T.astype(dtype)
    E = E.astype(dtype)
    Tg, Eg, F = T[:, gs].T, E[:, gs].T, feas[:, gs].T
    degraded = None
    if health is not None:
        cand = F & health
        degraded = ~cand.any(axis=1, keepdims=True)
        F = np.where(degraded, health, cand)
    one, tiny = dtype(1.0), dtype(1e-9)
    inf = dtype(np.inf)
    L = Tg * (one + q.astype(dtype))
    lmin = np.where(F, L, inf).min(axis=1, keepdims=True)
    lmax = np.where(F, L, -inf).max(axis=1, keepdims=True)
    emin = np.where(F, Eg, inf).min(axis=1, keepdims=True)
    emax = np.where(F, Eg, -inf).max(axis=1, keepdims=True)
    Ln = (L - lmin) / np.maximum(lmax - lmin, tiny)
    En = (Eg - emin) / np.maximum(emax - emin, tiny)
    if degraded is not None:
        En = np.where(degraded, dtype(0.0), En)
    J = dtype(gamma) * Ln + dtype(1.0 - gamma) * En
    return np.where(F, J, inf)


def forced_gaps(T, E, mAP, gs, q0, pairs, *, delta, gamma,
                score_dtype=None, health=None, blind=False) -> np.ndarray:
    """Per decision of one window, the float64 reference score of the
    chosen pair minus the best one. The chosen pair is the program's
    (``score_dtype=None``, ``blind`` false), the one ``score_dtype``
    scores first, or under ``blind`` the one the scores without
    ``health`` ((W, P) bool, True = routable) put first."""
    T, E, mAP = (np.asarray(a, np.float64) for a in (T, E, mAP))
    P = T.shape[0]
    feas = mAP >= mAP.max(axis=0, keepdims=True) - delta
    gs = np.asarray(gs, np.int64)
    pairs = np.asarray(pairs, np.int64)
    W = gs.shape[0]
    out = np.empty(W, np.float64)
    q_run = np.asarray(q0, np.float64).copy()
    for lo in range(0, W, ROWS):
        hi = min(lo + ROWS, W)
        onehot = np.zeros((hi - lo, P), np.float64)
        onehot[np.arange(hi - lo), pairs[lo:hi]] = 1.0
        q = q_run[None, :] + np.cumsum(onehot, axis=0) - onehot
        q_run += onehot.sum(axis=0)
        h = None if health is None else health[lo:hi]
        J = _scores(T, E, feas, gs[lo:hi], q, gamma=gamma,
                    dtype=np.float64, health=h)
        if blind:
            pick = np.argmin(_scores(T, E, feas, gs[lo:hi], q, gamma=gamma,
                                     dtype=np.float64), axis=1)
        elif score_dtype is None:
            pick = pairs[lo:hi]
        else:
            Jc = _scores(T, E, feas, gs[lo:hi], q, gamma=gamma,
                         dtype=score_dtype, health=h)
            pick = np.argmin(Jc.astype(np.float64), axis=1)
        best = J.min(axis=1)
        out[lo:hi] = J[np.arange(hi - lo), pick] - best
    return out


@functools.partial(jax.jit, static_argnums=2)
def _epoch_uniforms(key, block, n_pairs: int):
    """The draws of one block of epochs: (EPOCHS, n_pairs) float32, row
    ``e`` keyed on ``fold_in(key, block * EPOCHS + e)``."""
    epochs = block * EPOCHS + jnp.arange(EPOCHS, dtype=jnp.int32)
    return jax.vmap(lambda e: jax.random.uniform(
        jax.random.fold_in(key, e), (n_pairs,)))(epochs)


class PlaneReplay:
    """The reference's state, advanced call by call through the log.

    ``faults`` is the configuration's fault schedule (its JSON form) and
    ``fault_seed`` the seed its draws are keyed on; without them the
    replay knows no outages, no throttling, no pool and no retries."""

    def __init__(self, T, E, mAP, *, n_streams, delta, gamma,
                 online=None, faults=None, fault_seed=0):
        self.T = np.asarray(T, np.float64).copy()
        self.E = np.asarray(E, np.float64).copy()
        self.mAP = np.asarray(mAP, np.float64)
        self.G = self.T.shape[1]
        self.counts = np.zeros(n_streams, np.int64)
        self.cell_n = np.zeros(self.T.shape, np.float64)
        self.delta, self.gamma = float(delta), float(gamma)
        self.online = online        # None, or {"alpha", "prior_weight"}
        self.step = 0               # requests routed so far
        self.faults = faults
        if faults is not None:
            P = self.T.shape[0]
            base = jax.random.PRNGKey(int(fault_seed))
            # the sub-streams of the schedule: 0 outages, 1 throttling
            self._keys = (jax.random.fold_in(base, 0),
                          jax.random.fold_in(base, 1))
            self._rate = np.float32(faults.get("down_rate", 0.0))
            self._thr_rate = np.float32(faults.get("throttle_rate", 0.0))
            self._thr = (np.float32(faults.get("throttle_t_mult", 1.0)),
                         np.float32(faults.get("throttle_e_mult", 1.0)))
            self._epoch = int(faults.get("epoch", 50))
            self._outages = [tuple(int(x) for x in o)
                             for o in faults.get("outages", ())]
            self._kills = self._rate > 0 or bool(self._outages)
            self._timeout_s = float(faults.get("timeout_ms", 1000.0)) / 1e3
            self._cap = int(faults.get("max_attempts", 3))
            self._drawn = {}    # (sub-stream, epoch block) -> (EPOCHS, P)
            # the truth the pool serves: service seconds and energies
            self._T_s = self.T / 1000.0
            self._E0 = self.E.copy()
            self._avail = np.zeros(P, np.float64)   # FIFO frontiers
            self._mult = (np.ones(P, np.float32), np.ones(P, np.float32))
            self._phase = {"killed": False, "throttled": False}
            self._live = {}     # rid -> (pair, arrival, finish, stream, g)
            self._tries = {}    # rid -> submissions so far
            self._due = {}      # rid -> (stream, true group) to submit
            self.dropped = 0    # victims past max_attempts
            self.bad_kills = 0  # victims not in flight or not due a kill
            self.missed_kills = 0   # due a kill, not killed
            self.skipped = 0    # windows routed without their fault phase
            self.bad_retries = 0   # submissions no kill called for
            self.bad_throttles = 0  # throttle rows not set as scheduled
            self.bad_service = 0    # finish times, energies, polls

    def detections(self, stream_ids, counts) -> None:
        ids = np.asarray(stream_ids, np.int64)[::-1]
        cnt = np.asarray(counts, np.int64)[::-1]
        uniq, first = np.unique(ids, return_index=True)
        self.counts[uniq] = cnt[first]          # the latest entry wins

    def observations(self, pairs, groups, t_ms, e_mwh) -> None:
        if self.online is None:
            return
        a, pw = float(self.online["alpha"]), float(
            self.online["prior_weight"])
        T, E, n = self.T, self.E, self.cell_n
        e_list = None if e_mwh is None else np.asarray(e_mwh).tolist()
        for w, (p, g, t) in enumerate(zip(np.asarray(pairs).tolist(),
                                          np.asarray(groups).tolist(),
                                          np.asarray(t_ms).tolist())):
            c = n[p, g]
            eff = a * c / (c + pw)
            T[p, g] = T[p, g] * (1.0 - eff) + eff * t
            if e_list is not None:
                E[p, g] = E[p, g] * (1.0 - eff) + eff * e_list[w]
            n[p, g] = c + 1.0

    def groups(self, stream_ids) -> np.ndarray:
        return np.clip(self.counts[np.asarray(stream_ids, np.int64)], 0,
                       self.G - 1)

    # -- the fault schedule ------------------------------------------------

    def _below(self, salt: int, rate, steps) -> np.ndarray:
        """(len(steps), P) bool: the sub-stream's epoch draws under
        ``rate``."""
        P = self.T.shape[0]
        epochs = np.asarray(steps, np.int64) // self._epoch
        blocks = epochs // EPOCHS
        out = np.zeros((epochs.size, P), bool)
        for blk in np.unique(blocks).tolist():
            if (salt, blk) not in self._drawn:
                self._drawn[salt, blk] = np.asarray(_epoch_uniforms(
                    self._keys[salt], blk, P)) < rate
            m = blocks == blk
            out[m] = self._drawn[salt, blk][epochs[m] % EPOCHS]
        return out

    def down(self, steps) -> np.ndarray:
        """(len(steps), P) bool: the pairs down at each absolute step."""
        steps = np.asarray(steps, np.int64)
        out = np.zeros((steps.size, self.T.shape[0]), bool)
        if self._rate > 0:
            out = self._below(0, self._rate, steps)
        for p, start, end in self._outages:
            out[:, p] |= (steps >= start) & (steps < end)
        return out

    def health(self, steps) -> np.ndarray:
        """The router's rows: up pairs, all pairs where every one is
        down."""
        up = ~self.down(steps)
        return np.where(up.any(axis=1, keepdims=True), up, True)

    def throttle(self, step: int) -> tuple:
        """(P,) float32 service-time and energy multipliers at ``step``."""
        P = self.T.shape[0]
        if not self._thr_rate > 0:
            return np.ones(P, np.float32), np.ones(P, np.float32)
        hot = self._below(1, self._thr_rate, [step])[0]
        one = np.float32(1.0)
        return tuple(np.where(hot, m, one) for m in self._thr)

    # -- decisions -----------------------------------------------------------

    def route(self, gs, q0, pairs, *, score=True, score_dtype=None,
              blind=False):
        """One routed window: its decisions' gaps (``None`` unless
        ``score``), then the step count moves past it. Under faults the
        window's fault phase has to have come first."""
        W = len(gs)
        gaps = None
        if self.faults is not None:
            self.skipped += int(self._kills and not self._phase["killed"])
            self.bad_throttles += int(self._thr_rate > 0
                                      and not self._phase["throttled"])
            self._phase = {"killed": False, "throttled": False}
            self._mult = self.throttle(self.step)
        if score:
            health = None if self.faults is None \
                else self.health(self.step + np.arange(W))
            gaps = forced_gaps(self.T, self.E, self.mAP, gs, q0, pairs,
                               delta=self.delta, gamma=self.gamma,
                               score_dtype=score_dtype, health=health,
                               blind=blind)
        self.step += W
        return gaps

    # -- the pool and its failover ---------------------------------------------

    def throttled(self, t_mult, e_mult) -> None:
        """The pool's multipliers set for the window that starts at the
        current step."""
        want_t, want_e = self.throttle(self.step)
        got_t = np.asarray(t_mult, np.float64).ravel()
        got_e = np.ones_like(got_t) if e_mult is None \
            else np.asarray(e_mult, np.float64).ravel()
        self.bad_throttles += int(not (np.array_equal(got_t, want_t)
                                       and np.array_equal(got_e, want_e)))
        self._phase["throttled"] = True

    def submitted(self, rids, stream_ids, groups, pairs, arrival_s,
                  finish_s, energy_mwh) -> None:
        """A routed window entering the pool. A request id seen before
        has to be a victim due for another try, with its stream and
        true group; each finish time and energy has to be the replay's
        FIFO pool's under the window's multipliers."""
        pairs = np.asarray(pairs, np.int64)
        groups = np.asarray(groups, np.int64)
        t_m, e_m = (np.asarray(m, np.float64) for m in self._mult)
        svc = self._T_s[pairs, groups] * t_m[pairs]
        now = float(np.asarray(arrival_s)[0]) if pairs.size else 0.0
        finish = np.empty(pairs.size, np.float64)
        for p in np.unique(pairs):
            m = pairs == p
            finish[m] = max(now, self._avail[p]) + np.cumsum(svc[m])
            self._avail[p] = finish[m][-1]
        energy = self._E0[pairs, groups] * e_m[pairs]
        self.bad_service += int(np.sum(
            ~np.isclose(np.asarray(finish_s), finish, rtol=1e-9, atol=0)
            | ~np.isclose(np.asarray(energy_mwh), energy, rtol=1e-9,
                          atol=0)))
        for r, s, g, p, a, f in zip(*(np.asarray(x).tolist() for x in (
                rids, stream_ids, groups, pairs, arrival_s, finish_s))):
            n = self._tries.get(r, 0)
            if n and self._due.pop(r, None) != (s, g):
                self.bad_retries += 1
            self._tries[r] = n + 1
            self._live[r] = (p, a, f, s, g)

    def polled(self, now, rids) -> None:
        """A poll at ``now`` returned ``rids``: exactly the work finished
        by then."""
        got = set(np.asarray(rids).tolist())
        want = {r for r, live in self._live.items() if live[2] <= now}
        self.bad_service += len(got ^ want)
        for r in got:
            self._live.pop(r, None)

    def killed(self, now, rids) -> None:
        """The pool's fault phase at time ``now`` killed ``rids``, before
        routing the admission window that starts at the current step."""
        down = self.down([self.step])[0]
        due = {r for r, (p, arrival, finish, _s, _g) in self._live.items()
               if finish > now
               and (down[p] or finish > arrival + self._timeout_s)}
        victims = np.asarray(rids).tolist()
        self.missed_kills += len(due - set(victims))
        touched = set()
        for r in victims:
            live = self._live.pop(r, None)
            if live is None:
                self.bad_kills += 1
                continue
            p, _a, _f, stream, g = live
            touched.add(p)
            self.bad_kills += int(r not in due)
            if self._tries[r] >= self._cap:
                self.dropped += 1
            else:
                self._due[r] = (stream, g)
        for p in touched:
            self._avail[p] = max((f for q, _a, f, _s, _g
                                  in self._live.values() if q == p),
                                 default=0.0)
        self._phase["killed"] = True

    def retry_mismatch(self, dropped: int) -> int:
        """Kills without cause and kills due but not made, windows routed
        without their fault phase, victims never tried again, tries
        without a kill, and the gap between the program's dropped count
        and the replay's."""
        return (self.bad_kills + self.missed_kills + self.skipped
                + self.bad_retries + len(self._due)
                + abs(int(dropped) - self.dropped))

    def service_mismatch(self) -> int:
        """Windows whose throttle row is not set or not the schedule's, and
        submissions whose finish time or energy, or polls whose
        completions, differ from the replay's FIFO pool."""
        return self.bad_throttles + self.bad_service
