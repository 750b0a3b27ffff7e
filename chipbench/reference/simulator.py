"""Plain reference of the what-if sweep engine, in ``jax.numpy``.

One config row is one closed-loop Locust-style experiment (paper §IV):
``n_users`` streams each keep one request in flight; the next request of
a stream is issued when its previous one returns. Per dispatch, in this
order:

1. the stream whose request returns first (ties: the lowest index)
   issues the next request at that time ``t``;
2. the row's key splits into (next key, k1, k2, k3);
3. the stream's true object count steps by the scene chain (a
   first-order Markov chain over the G groups; ``jax.random.categorical``
   with key k1 over the log of the count's row, plus 1e-9); its true
   group is ``clip(count, 0, G - 1)``;
4. the estimated group is ``clip(c, 0, G - 1)`` of the count the
   detector reported on the stream's previous frame;
5. the queue depth of a pair is the number of streams whose last request
   went to it and returns after ``t``;
6. the policy picks a pair from the estimated group and the depths
   (Algorithm 1 for MO; round robin, uniform random with key k2, least
   connections, least energy, least expected latency, highest accuracy
   for the baselines);
7. the request starts when the pair is free, takes ``T[p, g_true] / 1000``
   seconds, and the detector reports each of the true objects (at most
   8) with probability ``0.8 + 0.2 mAP / 100`` (uniforms from k3) plus a
   false positive with probability ``0.05 (1 - mAP / 100)`` (a uniform
   from ``fold_in(k3, 1)``).

Initial states: the chain's stationary distribution (200 steps of power
iteration from uniform), drawn per row with ``categorical`` under
``split(PRNGKey(seed))[0]``; the row's scan key is the other half. A
row that is one block of a config with more users than the block size
draws user ``u``'s state under ``fold_in(k_init, u)`` and scans under
``fold_in(key, block)``.

Summaries drop the first ``warmup`` requests of a row and are computed
on the host in float64. It imports nothing of the program; ``dtype``
sets the precision of the times and scores (bfloat16 makes the
control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

POLICIES = {"MO": 0, "RR": 1, "RND": 2, "LC": 3, "LE": 4, "LT": 5, "HA": 6}
MAX_COUNT = 8


def markov_transition(G: int, stickiness, drift_up: float = 0.62):
    eye = jnp.eye(G)
    up = jnp.roll(eye, 1, axis=1).at[-1].set(0.0)
    down = jnp.roll(eye, -1, axis=1).at[0].set(0.0)
    drift = drift_up * up + (1 - drift_up) * down
    drift = drift.at[0, 1].set(1.0).at[-1, -2].set(1.0)
    jump = jnp.ones((G, G)) / G
    P = stickiness * eye + (1 - stickiness) * (0.8 * drift + 0.2 * jump)
    return P / jnp.sum(P, axis=1, keepdims=True)


def stationary(P):
    pi = jnp.ones((P.shape[0],)) / P.shape[0]
    return jax.lax.fori_loop(
        0, 200, lambda _, p: jnp.dot(p, P, precision="highest"), pi)


@functools.partial(jax.jit, static_argnames=("G", "n_users"))
def _initial(seed, stickiness, *, G: int, n_users: int):
    P = markov_transition(G, stickiness)
    k_init, key = jax.random.split(jax.random.PRNGKey(seed))
    true0 = jax.random.categorical(k_init, jnp.log(stationary(P) + 1e-9),
                                   shape=(n_users,))
    return true0.astype(jnp.int32), key


@functools.partial(jax.jit, static_argnames=("G", "n_users"))
def _initial_blocked(seed, stickiness, *, G: int, n_users: int):
    P = markov_transition(G, stickiness)
    k_init, key = jax.random.split(jax.random.PRNGKey(seed))
    logits = jnp.log(stationary(P) + 1e-9)
    true0 = jax.vmap(lambda u: jax.random.categorical(
        jax.random.fold_in(k_init, u), logits))(jnp.arange(n_users))
    return true0.astype(jnp.int32), key


def rows(configs, G: int, block: int | None):
    """Host arrays of the grid's rows: one per config, or one per block
    of ``block`` users. ``configs`` is a list of dicts with ``policy``,
    ``n_users``, ``seed``, ``gamma``, ``delta``, ``stickiness``. Returns
    ``(rows, segment)``: a dict of (R, ...) arrays and each row's
    config index."""
    out = {k: [] for k in ("policy", "n_users", "gamma", "delta",
                           "stickiness", "key", "true0")}
    segment = []
    U = max(c["n_users"] for c in configs) if block is None \
        else min(block, max(c["n_users"] for c in configs))
    cache: dict = {}
    for ci, c in enumerate(configs):
        n = int(c["n_users"])
        multi = block is not None and n > block
        ck = (int(c["seed"]), float(c["stickiness"]), n, multi)
        if ck not in cache:
            fn = _initial_blocked if multi else _initial
            t0, key = fn(jnp.int32(c["seed"]), jnp.float32(c["stickiness"]),
                         G=G, n_users=n)
            sizes = [n] if not multi else \
                [min(block, n - lo) for lo in range(0, n, block)]
            keys = np.asarray(jax.vmap(jax.random.fold_in, (None, 0))(
                key, jnp.arange(len(sizes)))) if multi \
                else np.asarray(key)[None]
            cache[ck] = (np.asarray(t0), keys, sizes)
        t0, keys, sizes = cache[ck]
        for b, size in enumerate(sizes):
            lo = b * (block or 0)
            row_t0 = np.zeros(U, np.int32)
            row_t0[:size] = t0[lo:lo + size]
            out["true0"].append(row_t0)
            out["key"].append(keys[b])
            out["policy"].append(POLICIES[c["policy"]])
            out["n_users"].append(size)
            for k in ("gamma", "delta", "stickiness"):
                out[k].append(float(c[k]))
            segment.append(ci)
    arr = {k: np.asarray(v) for k, v in out.items()}
    arr["key"] = arr["key"].astype(np.uint32)
    return arr, np.asarray(segment)


def _row(tables, r, *, n_requests: int, dtype):
    T, E, mAP = (tables[k] for k in ("T", "E", "mAP"))
    P, G = T.shape
    U = r["true0"].shape[0]
    Td = T.astype(dtype)
    Ed = E.astype(dtype)
    trans = markov_transition(G, r["stickiness"])
    code = r["policy"]
    live = jnp.arange(U) < r["n_users"]
    inf = jnp.asarray(jnp.inf, dtype)

    def choose(g, q, k2, rr):
        Tg, Eg, Mg = Td[:, g], Ed[:, g], mAP[:, g]
        feas = Mg >= jnp.max(Mg) - r["delta"]
        L = Tg * (1 + q.astype(dtype))
        lmin = jnp.min(jnp.where(feas, L, inf))
        lmax = jnp.max(jnp.where(feas, L, -inf))
        emin = jnp.min(jnp.where(feas, Eg, inf))
        emax = jnp.max(jnp.where(feas, Eg, -inf))
        tiny = jnp.asarray(1e-9, dtype)
        Ln = (L - lmin) / jnp.maximum(lmax - lmin, tiny)
        En = (Eg - emin) / jnp.maximum(emax - emin, tiny)
        gamma = r["gamma"].astype(dtype)
        J = gamma * Ln + (1 - gamma) * En
        mo = jnp.argmin(jnp.where(feas, J, inf))
        rnd = jnp.argmin(jax.random.uniform(k2, (P,)))
        lt = jnp.argmin(Tg * (1 + q.astype(dtype)))
        picks = jnp.stack([mo, rr % P, rnd, jnp.argmin(q),
                           jnp.argmin(jnp.mean(E, axis=1)), lt,
                           jnp.argmin(-jnp.mean(mAP, axis=1))])
        return picks[code].astype(jnp.int32)

    carry = {
        "t_next": jnp.where(live, jnp.arange(U, dtype=jnp.float32) * 1e-4,
                            jnp.inf).astype(dtype),
        "true": r["true0"], "est": r["true0"],
        "server": jnp.full((U,), -1, jnp.int32),
        "finish": jnp.zeros((U,), dtype),
        "avail": jnp.zeros((P,), dtype),
        "rr": jnp.int32(0), "key": r["key"],
    }

    def step(c, _):
        u = jnp.argmin(c["t_next"])
        t = c["t_next"][u]
        key, k1, k2, k3 = jax.random.split(c["key"], 4)
        cur = c["true"][u]
        new = jax.random.categorical(
            k1, jnp.log(trans[cur[None]] + 1e-9), axis=-1)[0]
        g_true = jnp.clip(new, 0, G - 1)
        g_est = jnp.clip(c["est"][u], 0, G - 1)
        busy = (c["finish"] > t) & (c["server"] >= 0)
        q = jnp.zeros((P,), jnp.float32).at[
            jnp.where(busy, c["server"], P)].add(1.0, mode="drop")
        p = choose(g_est, q, k2, c["rr"])
        start = jnp.maximum(t, c["avail"][p])
        finish = start + Td[p, g_true] / 1000.0
        m = mAP[p, g_true]
        p_det = jnp.clip(0.80 + 0.20 * m / 100.0, 0.0, 1.0)
        seen = jnp.sum((jax.random.uniform(k3, (MAX_COUNT,)) < p_det)
                       & (jnp.arange(MAX_COUNT) < new))
        fp = jax.random.uniform(jax.random.fold_in(k3, 1), ()) \
            < 0.05 * (1.0 - m / 100.0)
        nc = {"t_next": c["t_next"].at[u].set(finish),
              "true": c["true"].at[u].set(new.astype(jnp.int32)),
              "est": c["est"].at[u].set((seen + fp).astype(jnp.int32)),
              "server": c["server"].at[u].set(p),
              "finish": c["finish"].at[u].set(finish),
              "avail": c["avail"].at[p].set(finish),
              "rr": c["rr"] + 1, "key": key}
        rec = {"t": t.astype(jnp.float32),
               "lat": (finish - t).astype(jnp.float32),
               "energy": E[p, g_true], "map": m,
               "hit": (g_true == g_est).astype(jnp.float32)}
        return nc, rec

    _, recs = jax.lax.scan(step, carry, None, length=n_requests)
    return recs


@functools.partial(jax.jit, static_argnames=("n_requests", "dtype"))
def _simulate(tables, rws, *, n_requests: int, dtype):
    return jax.vmap(lambda r: _row(tables, r, n_requests=n_requests,
                                   dtype=dtype))(rws)


def simulate(tables: dict, rws: dict, *, n_requests: int,
             dtype=jnp.float32, devices=None) -> dict:
    """Per-row records ``{"t", "lat", "energy", "map", "hit"}`` as (R, N)
    host arrays. The rows are split evenly over ``devices`` (default:
    every local device; the last part padded with copies of the last
    row) and the parts run at once, one on each device."""
    devices = list(devices or jax.local_devices())
    R = rws["policy"].shape[0]
    n = min(len(devices), R)
    per = -(-R // n)
    host = {k: np.asarray(v) for k, v in rws.items()}
    for k in ("policy", "n_users"):
        host[k] = host[k].astype(np.int32)
    for k in ("gamma", "delta", "stickiness"):
        host[k] = host[k].astype(np.float32)
    if n * per > R:
        host = {k: np.concatenate([v, np.repeat(v[-1:], n * per - R, 0)])
                for k, v in host.items()}
    parts = []
    for i, dev in enumerate(devices[:n]):
        tb = {k: jax.device_put(np.asarray(tables[k], np.float32), dev)
              for k in ("T", "E", "mAP")}
        part = {k: jax.device_put(v[i * per:(i + 1) * per], dev)
                for k, v in host.items()}
        parts.append(_simulate(tb, part, n_requests=n_requests, dtype=dtype))
    return {k: np.concatenate([np.asarray(p[k]) for p in parts])[:R]
            for k in parts[0]}


def summaries(recs: dict, segment, n_configs: int, floor_mw, *,
              warmup: int) -> dict:
    """Per-config metrics in float64: latency mean and p90 (ms, over all
    the config's requests after each row's warm-up), throughput
    (req/s, summed over rows), energy (mWh: per-request energy plus the
    fleet's floor power over each row's makespan, averaged over rows),
    mAP, estimator accuracy and makespan (s, the longest row's)."""
    t = recs["t"][:, warmup:].astype(np.float64)
    lat = recs["lat"][:, warmup:].astype(np.float64)
    n_eff = t.shape[1]
    span = (t + lat).max(axis=1) - t.min(axis=1)
    floor = float(np.sum(np.asarray(floor_mw, np.float64)))
    row = {"latency_ms": 1e3 * lat.mean(axis=1),
           "throughput_rps": n_eff / span,
           "energy_mwh": recs["energy"][:, warmup:].astype(
               np.float64).mean(axis=1) + floor * span / 3600.0 / n_eff,
           "map": recs["map"][:, warmup:].astype(np.float64).mean(axis=1),
           "estimator_acc": recs["hit"][:, warmup:].astype(
               np.float64).mean(axis=1),
           "makespan_s": span}
    out = {k: np.zeros(n_configs) for k in row}
    out["latency_p90_ms"] = np.zeros(n_configs)
    for ci in range(n_configs):
        m = segment == ci
        for k, v in row.items():
            out[k][ci] = v[m].sum() if k == "throughput_rps" else (
                v[m].max() if k == "makespan_s" else v[m].mean())
        out["latency_p90_ms"][ci] = 1e3 * np.percentile(lat[m], 90)
    return out
