"""Serving driver: the paper's fleet under a chosen policy.

  PYTHONPATH=src python -m repro.launch.serve --policy MO --users 15 \
      --requests 500 --mode real
"""

from __future__ import annotations

import argparse
import json

from repro.core.profiles import paper_fleet, synthetic_fleet
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import ServingEngine


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="MO")
    ap.add_argument("--gamma", type=float, default=0.5)
    ap.add_argument("--delta", type=float, default=20.0)
    ap.add_argument("--users", type=int, default=8)
    ap.add_argument("--requests", type=int, default=300)
    ap.add_argument("--mode", default="modelled", choices=["modelled", "real"])
    ap.add_argument("--online", action="store_true")
    ap.add_argument("--fleet", default="paper", choices=["paper", "synthetic"])
    ap.add_argument("--n-pairs", type=int, default=32)
    a = ap.parse_args()

    if a.fleet == "paper":
        prof = paper_fleet()
        tiers = ["ssd_v1", "ssd_lite", "yolo_s", "yolo_s", "ssd_v1"]
    else:
        import jax
        prof = synthetic_fleet(jax.random.PRNGKey(0), a.n_pairs)
        tiers = ["ssd_v1"] * prof.n_pairs

    eng = ServingEngine.build(prof, policy=a.policy, gamma=a.gamma,
                              delta=a.delta, n_streams=a.users, mode=a.mode,
                              tiers=tiers, online=a.online)
    recs = eng.run(n_requests=a.requests, concurrency=a.users)
    out = eng.summarize(recs)
    out.update(policy=a.policy, users=a.users, mode=a.mode)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
