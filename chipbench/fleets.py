"""The fleets the configurations describe, made by the benchmark.

A configuration either lists its profile tables (``"tables"``: T in ms,
E in mWh, mAP in points, per pair and scene group, and each pair's
active-floor power in mW), names a generator with its arguments
(``"generator"``), or names another configuration whose fleet it runs
(``"fleet"``: a deployment that adds, say, a fault schedule to a fleet
the benchmark already holds; ``bench.load_config`` puts that
configuration in the name's place). The generator here is a copy of the
repository's ``synthetic_fleet`` scale-test generator, run as one jitted
call on the device from the configuration's own key, so the program and
the reference get the same tables without the reference taking anything
the program made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit,
                   static_argnames=("n_pairs", "n_groups", "frac_strong"))
def _synthetic(key, *, n_pairs: int, n_groups: int, frac_strong: float):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    strong = jax.random.uniform(k1, (n_pairs, 1)) < frac_strong
    base_t = jnp.where(strong, 120.0, 40.0) \
        * jax.random.uniform(k2, (n_pairs, 1), minval=0.7, maxval=1.4)
    slope = jnp.linspace(1.0, 1.3, n_groups)[None, :]
    T = base_t * slope
    E = jnp.where(strong, 0.28, 0.09) \
        * jax.random.uniform(k3, (n_pairs, 1), minval=0.6, maxval=1.4) \
        * slope
    g = jnp.linspace(0.0, 1.0, n_groups)[None, :]
    strong_map = 74.0 + 6.0 * g
    weak_map = 70.0 - 60.0 * g
    noise = jax.random.uniform(k4, (n_pairs, n_groups), minval=-3,
                               maxval=3)
    mAP = jnp.clip(jnp.where(strong, strong_map, weak_map) + noise, 1.0,
                   99.0)
    floor = jnp.where(strong[:, 0], 500.0, 150.0)
    return T, E, mAP, floor


def tables(config: dict) -> dict:
    """``{"T", "E", "mAP", "floor_mw"}`` float32 NumPy arrays of the
    configuration's fleet."""
    if "fleet" in config:
        if isinstance(config["fleet"], str):
            raise ValueError(f"fleet {config['fleet']!r} is a name: load "
                             f"the configuration with bench.load_config")
        return tables(config["fleet"])
    if "tables" in config:
        t = config["tables"]
        return {k: np.asarray(t[k], np.float32)
                for k in ("T", "E", "mAP", "floor_mw")}
    gen = config["generator"]
    if gen["name"] != "synthetic_fleet":
        raise ValueError(f"unknown fleet generator {gen['name']!r}")
    out = _synthetic(jax.random.PRNGKey(int(gen["key"])),
                     n_pairs=int(gen["n_pairs"]),
                     n_groups=int(gen["n_groups"]),
                     frac_strong=float(gen["frac_strong"]))
    return dict(zip(("T", "E", "mAP", "floor_mw"),
                    (np.asarray(a, np.float32) for a in out)))


def profile_table(tb: dict):
    """The program's ``ProfileTable`` over the benchmark's tables."""
    from repro.core.profiles import ProfileTable

    P = tb["T"].shape[0]
    return ProfileTable(jnp.asarray(tb["T"]), jnp.asarray(tb["E"]),
                        jnp.asarray(tb["mAP"]),
                        tuple(f"pair{i}" for i in range(P)),
                        jnp.asarray(tb["floor_mw"]))
