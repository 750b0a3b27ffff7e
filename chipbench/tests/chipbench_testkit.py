"""Small cells for the benchmark's CPU tests: a checkout of their own in
a temporary directory, holding a copy of the benchmark's files, tiny
configurations and traffic mixes, and a ``BENCHMARK.json`` naming them
with the metrics of the served and the sweep kinds.

Each traffic kind's driver names its own tiny mix (``TINY_MIX``: the
traffic file it shrinks and the keys it sets); the checkout holds a cell
``tiny_<kind>`` of it for every driver it finds, so a kind that a later
change adds as a driver file is taken up with no edit here."""

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
#: the paper testbed with a fault schedule, named by its fleet: pairs go
#: down every other window on average, so kills, retries and drops all
#: come within a second
TINY_FAULTS = {"name": "paper_testbed_faults", "fleet": "paper_testbed",
               "faults": {"down_rate": 0.5, "epoch": 16,
                          "throttle_rate": 0.2, "throttle_t_mult": 1.6,
                          "throttle_e_mult": 1.0, "timeout_ms": 2000.0,
                          "max_attempts": 2}}


def driver_kinds(root: Path) -> list:
    """The traffic kinds of a checkout: one per driver module."""
    return sorted(p.stem for p in (root / "chipbench" / "drivers")
                  .glob("*.py") if not p.stem.startswith("_"))


def tiny_mix(root: Path, kind: str, traffic: str | None = None) -> dict:
    """The kind's tiny mix: its ``TINY_MIX`` keys set on the traffic file
    it names, or on ``traffic``."""
    from chipbench import bench

    mix = bench.load_module(root / "chipbench" / "drivers"
                            / f"{kind}.py").TINY_MIX
    t = json.loads((root / "chipbench" / "traffic"
                    / f"{traffic or mix['traffic']}.json").read_text())
    t.update(mix["set"])
    return t


def make_checkout(tmp: Path, files: dict | None = None) -> Path:
    """A checkout with a tiny cell ``tiny_<kind>`` of every traffic kind,
    the cells ``tiny_static``, ``tiny_online`` (the paper testbed's
    served mixes, short calls), ``tiny_faults`` (the online mix on
    ``paper_testbed_faults``) and ``tiny_blocked``, and a small generated
    fleet ``tiny_city`` for cells that tests add. ``files`` (path under
    the checkout -> text) are written first, as a change would add
    them."""
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for rel, text in (files or {}).items():
        (tmp / rel).write_text(text)
    bench = tmp / "chipbench"
    city = json.loads((bench / "configs" / "city_fleet.json").read_text())
    city.update(name="tiny_city")
    city["generator"]["n_pairs"] = 16
    (bench / "configs" / "tiny_city.json").write_text(json.dumps(city))
    (bench / "configs" / "paper_testbed_faults.json").write_text(
        json.dumps(TINY_FAULTS))
    faults = tiny_mix(tmp, "served", "served_paper_online")
    faults["limits"].update(retry_mismatch=0, service_mismatch=0)
    mixes = {f"tiny_{kind}": tiny_mix(tmp, kind)
             for kind in driver_kinds(tmp)}
    mixes.update(
        tiny_static=tiny_mix(tmp, "served", "served_paper_static"),
        tiny_online=tiny_mix(tmp, "served", "served_paper_online"),
        tiny_faults=faults,
        tiny_blocked=dict(json.loads((bench / "traffic" / "sweep_sites.json")
                                     .read_text()), **TINY_BLOCKED))
    for name, body in mixes.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(body))
    served = sorted(n for n, t in mixes.items() if t["kind"] == "served")
    sweeps = sorted(n for n, t in mixes.items() if t["kind"] == "sweep")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": n, "source": "https://arxiv.org/abs/2603.15400",
         "file": f"chipbench/configs/{n}.json", "reduced": []}
        for n in ("tiny_city", "paper_testbed", "paper_testbed_faults")]
    spec["workloads"] = [
        {"name": n, "config": "paper_testbed_faults" if n == "tiny_faults"
         else "paper_testbed", "traffic": n, "chips": 1}
        for n in sorted(mixes)]
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


def check_kind(root: Path, kind: str) -> set:
    """What ``run.py`` asks of every traffic kind's driver, on the kind's
    tiny cell: the driver's parts, a short window's metrics, its checks
    against the mix's limits, and a whole run through ``run.py`` that is
    correct and reports the end-to-end metrics ``BENCHMARK.json`` gives
    the cell. Returns the end-to-end metrics the driver reports (with
    ``setup_s``, which ``run.py`` adds)."""
    from chipbench import bench
    from chipbench.spans import Recorder

    name = f"tiny_{kind}"
    cell = bench.load_cell(root, name)
    assert cell.traffic["kind"] == kind
    drv = bench.driver(cell).setup(cell.config, cell.traffic, 3, Recorder())
    reported = set(drv.window(0.2)) | {"setup_s"}
    attempted, failed = drv.attempted()
    assert attempted > 0 and 0 <= failed <= attempted
    assert isinstance(drv.counts(), dict)
    assert drv.controls() and all(isinstance(c, str)
                                  for c in drv.controls())
    checks = drv.check()
    assert set(checks) == set(cell.traffic["limits"])
    assert all(checks[k] <= v for k, v in cell.traffic["limits"].items())
    rc, res, err = run_cell(root, name, seconds=0.5)
    assert rc == 0 and res["correct"], err
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) <= reported
    return reported
