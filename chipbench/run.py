"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are read from ``BENCHMARK.json`` and the files it names.
Set-up (imports, device start, building the system from the seed and
the warm-up) counts as ``setup_s``; then the cell's driver runs the
measured window for ``--seconds``. With ``--trace 1`` the window (cut
to the traffic mix's ``trace_seconds``, so that reading the trace stays
short) runs under the JAX profiler with spans around the program's
layers, and the result carries the cell's per-layer metrics and a
``breakdown``; with ``--trace 0`` it carries the end-to-end metrics.

After the window the run reads the device's peak memory, then checks
what the timed path produced against the plain reference. The numbers
compared, each beside its limit, are the last lines on standard error
and the last key of the result, which is the last line on standard
output.

Needs the accelerator: exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def check_device(chips: int) -> list:
    """The devices the cell runs on; raises :class:`NoChip` unless JAX
    finds TPUs, at least ``chips`` of them, of a kind with known peaks."""
    import jax

    from chipbench.roofline import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU, only {devices[0].platform} "
                     f"devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:chips]


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _limits_line(checks: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}


def run(args, *, root: Path = ROOT, require_chip: bool = True,
        cache: bool = True, t_start: float = T_START, out=sys.stdout,
        err=sys.stderr) -> int:
    _paths()
    import jax

    if cache:
        if not os.environ.get(CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        # small programs too, so a cell's later runs load every one
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from chipbench import bench, trace_reduce
    from chipbench.spans import CompileCounter, Recorder

    cell = bench.load_cell(root, args.workload)
    devices = check_device(cell.chips) if require_chip \
        else jax.devices()[:cell.chips]
    compiles = CompileCounter()
    rec = Recorder()
    drv = bench.driver(cell).setup(cell.config, cell.traffic, args.seed, rec)
    setup_s = time.perf_counter() - t_start

    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(cell.traffic["trace_seconds"]))
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        rec.timing = True
        jax.profiler.start_trace(
            trace_dir, profiler_options=_profile_options())
    t0 = time.perf_counter()
    with rec.span("window"):
        e2e = drv.window(seconds)
    t1 = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
        rec.timing = False
    memory_peak = _memory_peak(devices)

    checks = drv.check()
    limits = cell.traffic["limits"]
    correct = all(checks[k] <= limits[k] for k in limits) \
        and set(checks) == set(limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    attempted, failed = drv.attempted()
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed)}
    if args.trace:
        try:
            red = trace_reduce.reduce(trace_reduce.load(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"reduced": red, "recorder": rec, "device_kind":
               dev.device_kind, "compiles": compiles.between(t0, t1),
               **drv.counts()}
        metrics = {}
        for m in cell.per_layer:
            v = bench.metric_reader(cell, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = trace_reduce.breakdown(red)
    else:
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = _limits_line(checks, limits)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=err)
    print(json.dumps(result), file=out, flush=True)
    return 0


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        return run(args)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
