"""Small cells for the benchmark's CPU tests: a checkout of their own in
a temporary directory, holding a copy of the benchmark's files, tiny
configurations and traffic mixes, and a ``BENCHMARK.json`` naming them
with the metrics of the served and the sweep kinds."""

from __future__ import annotations

import io
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_SERVED = {"windows_per_call": 8, "warmup_calls": 1}
TINY_SWEEP = {"n_users": [1, 5, 15], "n_requests": 300,
              "seed_offsets": [0, 1]}
SERVED_LAYER_METRICS = (
    ("plane_observe_ms", "ms/window", "program_span", "plane_req_per_s"),
    ("plane_pool_ms", "ms/window", "program_span", "plane_req_per_s"),
    ("plane_route_ms", "ms/window", "program_span", "plane_req_per_s"),
    ("compiles_per_window", "count/window", "program_counter",
     "plane_req_per_s"),
    ("moscore_us_per_decision", "us/decision", "device_trace",
     "plane_req_per_s"),
    ("moscore_roofline_pct", "%", "device_trace", "plane_req_per_s"),
    ("device_idle_pct.serve", "%", "device_trace", "plane_req_per_s"))
SWEEP_LAYER_METRICS = (
    ("device_idle_pct.sweep", "%", "device_trace", "sweep_req_per_s"),
    ("sweep_scan_ms", "ms/grid", "device_trace", "sweep_req_per_s"))
TINY_BLOCKED = {"policies": ["MO", "HA"], "n_users": [23], "user_block": 5,
                "mesh": None, "n_requests": 200}


def _traffic(name: str, **over) -> dict:
    t = json.loads((REPO / "chipbench" / "traffic" / f"{name}.json")
                   .read_text())
    t.update(over)
    return t


def make_checkout(tmp: Path) -> Path:
    """A checkout with the tiny cells ``tiny_static``, ``tiny_online``
    (the paper testbed's served mixes, short calls), ``tiny_sweep`` and
    ``tiny_blocked``, and a small generated fleet ``tiny_city`` for cells
    that tests add."""
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = tmp / "chipbench"
    city = json.loads((bench / "configs" / "city_fleet.json").read_text())
    city.update(name="tiny_city")
    city["generator"]["n_pairs"] = 16
    (bench / "configs" / "tiny_city.json").write_text(json.dumps(city))
    for name, body in (
            ("tiny_static", _traffic("served_paper_static", **TINY_SERVED)),
            ("tiny_online", _traffic("served_paper_online", **TINY_SERVED)),
            ("tiny_sweep", _traffic("sweep_fig4", **TINY_SWEEP)),
            ("tiny_blocked", _traffic("sweep_sites", **TINY_BLOCKED))):
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(body))
    served = ["tiny_static", "tiny_online"]
    sweeps = ["tiny_sweep", "tiny_blocked"]
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": "tiny_city", "source": "https://arxiv.org/abs/2603.15400",
         "file": "chipbench/configs/tiny_city.json", "reduced": []},
        {"name": "paper_testbed",
         "source": "https://arxiv.org/abs/2603.15400",
         "file": "chipbench/configs/paper_testbed.json", "reduced": []}]
    spec["workloads"] = [
        {"name": n, "config": "paper_testbed", "traffic": n, "chips": 1}
        for n in served + sweeps]
    spec["end_to_end"] = [
        {"name": n, "unit": u, "better": b, "bound": 0.1,
         "source": "host_clock", "workloads": cells}
        for n, u, b, cells in (
            ("plane_req_per_s", "req/s", "higher", served),
            ("sweep_req_per_s", "req/s", "higher", sweeps))] + [
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}]
    spec["per_layer"] = [
        {"name": n, "unit": u, "better": "lower", "source": src,
         "layer": "x", "moves": moves,
         "workloads": sweeps if moves == "sweep_req_per_s" else served}
        for n, u, src, moves in SERVED_LAYER_METRICS + SWEEP_LAYER_METRICS]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_cell(root: Path, workload: str, *, seed: int = 7,
             seconds: float = 1.0, trace: int = 0):
    """One run of ``run.py`` in this process, without the chip check;
    returns ``(exit code, result dict or None, stderr text)``."""
    from chipbench import run as runner

    args = runner.parse(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace)])
    out, err = io.StringIO(), io.StringIO()
    rc = runner.run(args, root=root, require_chip=False, cache=False,
                    out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
