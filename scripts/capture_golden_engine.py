"""Capture the engine's golden fixtures under ``tests/``: record streams
and sweep metrics of the default engine (``golden_markov_pr2.json``,
``golden_static_pr3.json``), of ``cloud=None`` scenarios
(``golden_cloud_pr7.json``), and of ``faults=None`` scenarios with and
without a cloud tier (``golden_faults_pr9.json``).

The fixtures pin every later engine change to these values bit for bit.
Regenerate them only when the values move for a reason outside the
engine, such as a JAX release that changes the PRNG stream, and then run
this script on the last commit whose code the fixtures should describe,
never on the change under test.

Usage: PYTHONPATH=src python scripts/capture_golden_engine.py
"""

import json
import subprocess
from pathlib import Path

import jax
import numpy as np

from repro.core.cloud import CloudTier
from repro.core.dispatch import OnlineDispatch
from repro.core.scenario import Scenario, Sweep, records, run

REPO = Path(__file__).resolve().parent.parent

# Record corners shared by the cloud and fault fixtures: baseline MO, the
# RND key stream, non-default gamma/delta, the oracle ablation, a
# single-block user_block config and online-EWMA dispatch.
_CORNERS = [
    Scenario(n_users=5, n_requests=120, policy="MO", seed=3),
    Scenario(n_users=9, n_requests=120, policy="RND", seed=1),
    Scenario(n_users=7, n_requests=120, policy="MO", gamma=0.25,
             delta=10.0, seed=0),
    Scenario(n_users=4, n_requests=120, policy="LT", seed=2,
             oracle_estimator=True),
    Scenario(n_users=6, n_requests=120, policy="LC", seed=5,
             user_block=16),
    Scenario(n_users=5, n_requests=120, policy="MO", seed=7,
             dispatch=OnlineDispatch()),
]

_ENGINE_SWEEP = dict(user_levels=(3, 7), seeds=(0, 1))


def captured_at() -> str:
    commit = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                            cwd=REPO, capture_output=True, text=True)
    where = commit.stdout.strip() or "an unversioned tree"
    return f"{where}, jax {jax.__version__}"


def _records(scenarios):
    return [{"scenario": sc.to_json(),
             "records": {k: np.asarray(v, np.float64).tolist()
                         for k, v in records(sc).items()}}
            for sc in scenarios]


def _config_records(configs):
    """The older fixtures key each entry by its Scenario kwargs."""
    return [{"config": cfg,
             "records": _records([Scenario(**cfg)])[0]["records"]}
            for cfg in configs]


def _sweep(base: Scenario, policies, *, user_levels, seeds, legacy=False):
    res = run(base, Sweep(policy=policies, n_users=user_levels, seed=seeds))
    # the legacy layout is the SWEEP_AXES product (policy, users, gamma,
    # delta, stickiness, seed) that the sweep_grid tests compare directly
    shape = (len(policies), len(user_levels), 1, 1, 1, len(seeds)) \
        if legacy else res[res.metric_names[0]].shape
    fix = {} if legacy else {"scenario": base.to_json()}
    fix.update(policies=list(policies), user_levels=list(user_levels),
               seeds=list(seeds), n_requests=base.n_requests,
               metrics={k: np.asarray(res[k]).reshape(shape).tolist()
                        for k in res.metric_names})
    return fix


def markov() -> dict:
    return {"records": _config_records(
                [dict(n_users=5, n_requests=120, policy="MO", seed=3),
                 dict(n_users=9, n_requests=120, policy="HA", seed=1)]),
            "sweep": _sweep(Scenario(n_requests=250), ("MO", "LT", "HA"),
                            legacy=True, **_ENGINE_SWEEP)}


def static() -> dict:
    return {"records": _config_records(
                [dict(n_users=5, n_requests=120, policy="MO", seed=3),
                 dict(n_users=9, n_requests=120, policy="RND", seed=1),
                 dict(n_users=7, n_requests=120, policy="MO", gamma=0.25,
                      delta=10.0, seed=0),
                 dict(n_users=4, n_requests=120, policy="LT", seed=2,
                      oracle_estimator=True),
                 dict(n_users=11, n_requests=120, policy="RR", seed=5)]),
            "sweep": _sweep(Scenario(n_requests=150),
                            ("MO", "RND", "LC", "LE", "HA"), legacy=True,
                            **_ENGINE_SWEEP)}


def cloud() -> dict:
    return {"records": _records(_CORNERS),
            "sweep": _sweep(Scenario(n_requests=150),
                            ("MO", "RR", "LC", "LT", "HA"), **_ENGINE_SWEEP)}


def faults() -> dict:
    with_cloud = [
        Scenario(n_users=6, n_requests=120, policy="MO", seed=4,
                 cloud=CloudTier()),
        Scenario(n_users=5, n_requests=120, policy="LT", seed=2,
                 cloud=CloudTier(rtt_ms=10.0)),
    ]
    return {"records": _records(_CORNERS + with_cloud),
            "sweep": cloud()["sweep"],
            "cloud_sweep": _sweep(
                Scenario(n_requests=150, cloud=CloudTier()), ("MO", "LT"),
                user_levels=(3, 7), seeds=(0,))}


FIXTURES = {"golden_markov_pr2.json": markov,
            "golden_static_pr3.json": static,
            "golden_cloud_pr7.json": cloud,
            "golden_faults_pr9.json": faults}


def main():
    for name, build in FIXTURES.items():
        out = REPO / "tests" / name
        out.write_text(json.dumps({"captured_at": captured_at(), **build()}))
        print(f"wrote {out} ({out.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
