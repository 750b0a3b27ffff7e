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

It imports nothing of the program. ``score_dtype`` computes the scores
in a lower precision to make the control: the pair that precision puts
first, judged by the float64 scores.
"""

from __future__ import annotations

import numpy as np

ROWS = 512   # decisions scored per block, to bound the (rows, P) temporaries


def _scores(T, E, feas, gs, q, *, gamma, dtype):
    """Algorithm-1 scores of a block of decisions: (rows, P)."""
    T = T.astype(dtype)
    E = E.astype(dtype)
    Tg, Eg, F = T[:, gs].T, E[:, gs].T, feas[:, gs].T
    one, tiny = dtype(1.0), dtype(1e-9)
    inf = dtype(np.inf)
    L = Tg * (one + q.astype(dtype))
    lmin = np.where(F, L, inf).min(axis=1, keepdims=True)
    lmax = np.where(F, L, -inf).max(axis=1, keepdims=True)
    emin = np.where(F, Eg, inf).min(axis=1, keepdims=True)
    emax = np.where(F, Eg, -inf).max(axis=1, keepdims=True)
    Ln = (L - lmin) / np.maximum(lmax - lmin, tiny)
    En = (Eg - emin) / np.maximum(emax - emin, tiny)
    J = dtype(gamma) * Ln + dtype(1.0 - gamma) * En
    return np.where(F, J, inf)


def forced_gaps(T, E, mAP, gs, q0, pairs, *, delta, gamma,
                score_dtype=None) -> np.ndarray:
    """Per decision of one window, the float64 reference score of the
    chosen pair minus the best one. The chosen pair is the program's
    (``score_dtype=None``) or the one ``score_dtype`` scores first."""
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
        J = _scores(T, E, feas, gs[lo:hi], q, gamma=gamma,
                    dtype=np.float64)
        if score_dtype is None:
            pick = pairs[lo:hi]
        else:
            Jc = _scores(T, E, feas, gs[lo:hi], q, gamma=gamma,
                         dtype=score_dtype)
            pick = np.argmin(Jc.astype(np.float64), axis=1)
        best = J.min(axis=1)
        out[lo:hi] = J[np.arange(hi - lo), pick] - best
    return out


class PlaneReplay:
    """The reference's state, advanced call by call through the log."""

    def __init__(self, T, E, mAP, *, n_streams, delta, gamma,
                 online=None):
        self.T = np.asarray(T, np.float64).copy()
        self.E = np.asarray(E, np.float64).copy()
        self.mAP = np.asarray(mAP, np.float64)
        self.G = self.T.shape[1]
        self.counts = np.zeros(n_streams, np.int64)
        self.cell_n = np.zeros(self.T.shape, np.float64)
        self.delta, self.gamma = float(delta), float(gamma)
        self.online = online        # None, or {"alpha", "prior_weight"}

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

    def gaps(self, gs, q0, pairs, score_dtype=None) -> np.ndarray:
        return forced_gaps(self.T, self.E, self.mAP, gs, q0, pairs,
                           delta=self.delta, gamma=self.gamma,
                           score_dtype=score_dtype)
