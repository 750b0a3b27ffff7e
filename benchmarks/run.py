"""Benchmark harness: one module per paper table/figure + kernels +
roofline. Prints CSV: name,<columns...>.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only SUITE]
                                          [--json PATH] [--sharded]
                                          [--workload {markov,trace}]
                                          [--dispatch {static,online}]
                                          [--scenario SPEC.json]

Each suite is documented in ``docs/benchmarks.md``.

Scenarios
---------
The sweep suites run against ONE base
:class:`repro.core.scenario.Scenario` assembled from the flags:
``--workload trace`` swaps the scene-complexity source to the bundled
recorded trace, ``--dispatch online`` swaps static offline tables for
the online-EWMA adaptive engine, ``--sharded`` sets the scenario's mesh
spec to ``"local"`` (shard the config axis across all local devices —
bit-identical results, only faster on >1 device). ``--scenario PATH``
loads a full ``Scenario.to_json`` spec instead (the other three flags
then layer on top only when explicitly given). Each suite overrides the
per-suite knobs (``n_requests``, sweep axes) via ``dataclasses.replace``
— the scenario is the single config object the whole harness shares.

Running benchmarks / CI
-----------------------
``--fast`` shrinks seeds/requests to CI size. ``--json PATH``
additionally writes a ``BENCH_*.json``-style artifact: per-suite CSV
rows plus wall-clock seconds (``suites.<name>.seconds``), environment
metadata, and the base scenario (``scenario`` spec + ``scenario_hash``)
— the format ``scripts/check_bench.py`` validates and diffs against the
committed baseline (``benchmarks/bench_baseline.json``), failing on >20%
slowdown per suite (per-suite ``--threshold`` overrides supported) and
refusing to compare artifacts whose scenario hashes differ. The GitHub
workflow (``.github/workflows/ci.yml``) runs three jobs: ruff lint +
docs link check, the tier-1 pytest suite, and this runner in ``--fast
--json`` mode, uploading the JSON as a build artifact so every commit
leaves a benchmark trajectory point:

  PYTHONPATH=src python -m benchmarks.run --fast --json bench.json
  python scripts/check_bench.py bench.json benchmarks/bench_baseline.json

The sweep suites (fig4/fig5/ablation/scale/sweep_sharded) run on the
scenario engine (``repro.core.scenario.run``): each grid is ONE jitted
vmap(simulate + summarize) device program, so a full Fig. 4 sweep costs
one compile + one launch instead of ~150. ``sweep_sharded`` reports the
engine's configs/sec single-device vs sharded, and the memoized/
vectorised grid-build rate — the headline throughput numbers the
regression gate tracks. See ``docs/sweep_engine.md``.
"""

import argparse
import json
import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer seeds/requests (CI mode)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write a JSON artifact (per-suite rows + "
                         "wall-clock + scenario hash) for CI / "
                         "scripts/check_bench.py")
    ap.add_argument("--sharded", action="store_true",
                    help="run the sweep suites sharded across all local "
                         "devices (Scenario mesh='local'; bit-identical "
                         "results)")
    ap.add_argument("--workload", choices=("markov", "trace"),
                    default=None,
                    help="scene-complexity source for the sweep suites: "
                         "the synthetic Markov chain (default) or the "
                         "bundled recorded trace")
    ap.add_argument("--dispatch", choices=("static", "online"),
                    default=None,
                    help="dispatch-state engine for the sweep suites: "
                         "static offline tables (default) or the "
                         "online-EWMA adaptive engine")
    ap.add_argument("--scenario", default=None, metavar="SPEC.json",
                    help="load the base scenario from a Scenario.to_json "
                         "spec file instead of assembling it from flags")
    args = ap.parse_args()

    from dataclasses import replace

    from repro.core.scenario import Scenario

    if args.scenario:
        with open(args.scenario) as f:
            base = Scenario.from_json(json.load(f))
    else:
        base = Scenario()
    if args.workload == "trace":
        from repro.data.traces import bundled_trace
        base = replace(base, workload=bundled_trace())
    elif args.workload == "markov":
        base = replace(base, workload=None)
    if args.dispatch == "online":
        from repro.core.dispatch import OnlineDispatch
        base = replace(base, dispatch=OnlineDispatch())
    elif args.dispatch == "static":
        base = replace(base, dispatch=None)
    if args.sharded:
        base = replace(base, mesh="local")

    from benchmarks import (ablation_delta, bench_kernels, bench_scale,
                            edge_cloud, fault_resilience, fig2_motivation,
                            fig4_baselines, fig5_gamma, online_drift,
                            roofline_summary, serving_throughput,
                            sweep_sharded, table1_pairs, workload_trace)

    suites = {
        "fig2": lambda: fig2_motivation.run(),
        "table1": lambda: table1_pairs.run(),
        "fig4": lambda: fig4_baselines.run(
            base, n_requests=600 if args.fast else 1500,
            seeds=(0,) if args.fast else (0, 1, 2)),
        "fig5": lambda: fig5_gamma.run(
            base, n_requests=600 if args.fast else 1500,
            seeds=(0,) if args.fast else (0, 1)),
        "ablation": lambda: ablation_delta.run(base),
        "edge_cloud": lambda: edge_cloud.run(
            base, n_requests=400 if args.fast else 1500,
            seeds=(0,) if args.fast else (0, 1, 2)),
        "fault_resilience": lambda: fault_resilience.run(
            base, n_requests=150 if args.fast else 600,
            seeds=(0, 1) if args.fast else (0, 1, 2)),
        "scale": lambda: bench_scale.run(),
        "sweep_sharded": lambda: sweep_sharded.run(),
        "workload_trace": lambda: workload_trace.run(
            n_requests=250 if args.fast else 400),
        "online_drift": lambda: online_drift.run(
            n_requests=800 if args.fast else 2000,
            seeds=(0,) if args.fast else (0, 1)),
        "serving_throughput": lambda: serving_throughput.run(
            base, n_requests=50_000 if args.fast else 200_000,
            window=512 if args.fast else 1024),
        "kernels": lambda: bench_kernels.run(),
        "roofline": lambda: roofline_summary.run(),
    }
    if args.only:
        if args.only not in suites:
            sys.exit(f"benchmarks.run: unknown suite {args.only!r} "
                     f"(choose from: {', '.join(suites)})")
        suites = {args.only: suites[args.only]}

    report: dict[str, dict] = {}
    for name, fn in suites.items():
        t0 = time.time()
        err = None
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            err = f"{type(e).__name__}: {e}"
            rows = []
            print(f"{name},ERROR,{err}", flush=True)
        seconds = time.time() - t0
        for row in rows:
            print(row, flush=True)
        print(f"bench.{name}.seconds,{seconds:.1f}", flush=True)
        report[name] = {"rows": rows, "seconds": round(seconds, 3),
                        "error": err}

    if args.json:
        import jax

        from repro.core.dispatch import OnlineDispatch as _OD
        from repro.core.workload import MarkovWorkload as _MW

        artifact = {
            "schema": "repro-bench/v1",
            "fast": bool(args.fast),
            # mode strings kept for readability / legacy baselines; the
            # scenario spec + hash are the authoritative identity
            "workload": "markov" if base.workload is None
                        or isinstance(base.workload, _MW) else "trace",
            "dispatch": "online" if isinstance(base.dispatch, _OD)
                        else "static",
            "scenario": base.to_json(),
            "scenario_hash": base.hash,
            "created_unix": round(time.time(), 1),
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "suites": report,
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"bench.artifact,{args.json}", flush=True)

    # a crashed suite fails the run (CI's bench job is only allow-failure
    # on the *timing* gate, not on the benchmarks themselves)
    errored = [k for k, v in report.items() if v["error"]]
    if errored:
        print(f"bench.errored,{';'.join(errored)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
