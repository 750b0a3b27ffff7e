"""Loading cells, configurations, traffic mixes and metrics by name from
files, a new cell and a new traffic kind added by files alone, a
deployment that names its fleet, the shape of ``BENCHMARK.json``, the
peaks table and the command's refusal of a machine without a TPU."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest
from chipbench_testkit import (REPO, TINY_FAULTS, check_kind, driver_kinds,
                               make_checkout, run_cell)

from chipbench import bench, fleets, roofline

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
#: a traffic kind of its own, as a later change would add it: one driver
#: file that runs a device program and one traffic file
ECHO_DRIVER = '''"""Traffic kind ``echo``: sums a vector on the device."""
import time

import jax.numpy as jnp
import numpy as np

TINY_MIX = {"traffic": "echo", "set": {"n": 64}}


class Echo:
    def __init__(self, traffic, seed):
        self.x = np.random.default_rng(seed).random(int(traffic["n"]))
        self.sums = []

    def window(self, seconds):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds or not self.sums:
            self.sums.append(float(jnp.sum(jnp.asarray(self.x))))
        return {"echo_per_s": len(self.sums) / (time.perf_counter() - t0)}

    def counts(self):
        return {"calls": len(self.sums)}

    def attempted(self):
        return len(self.sums), 0

    def controls(self):
        return ("float16",)

    def check(self, control=None):
        want = float(np.sum(self.x))
        sums = self.sums if control is None \
            else [float(np.sum(self.x.astype(np.float16)))]
        return {"echo_gap": max(abs(s - want) / want for s in sums)}


def setup(config, traffic, seed, rec):
    return Echo(traffic, seed)
'''
ECHO_TRAFFIC = {"kind": "echo", "n": 4096, "trace_seconds": 1,
                "limits": {"echo_gap": 1e-5}}


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    texts = [e["why"] for e in SPEC["workloads"] + SPEC["configs"]]
    texts += [m["layer"] for m in SPEC["per_layer"]]
    texts += [c["source"] for c in SPEC["configs"]] + SPEC["command"]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    assert all(0.01 <= m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_every_cell_loads_with_its_files():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        cell = bench.load_cell(REPO, w["name"])
        assert cell.chips in (1, 4)
        assert bench.driver(cell).setup
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported and m["moves"] in e2e
            assert bench.metric_reader(cell, m["name"]).read
        assert set(cell.traffic["limits"])
        assert cell.config["name"] == w["config"]


@pytest.fixture(scope="module")
def reported_by_kind(tmp_path_factory):
    """The end-to-end metrics a traffic kind's driver reports, with
    ``setup_s``, which ``run.py`` adds; read once a kind, on its tiny
    cell, while ``check_kind`` holds the driver to its contract."""
    root = make_checkout(tmp_path_factory.mktemp("kinds"))
    seen = {}

    def reported(kind):
        if kind not in seen:
            seen[kind] = check_kind(root, kind)
        return seen[kind]
    return reported


@pytest.mark.parametrize("kind", driver_kinds(REPO))
def test_every_kind_keeps_the_driver_contract(reported_by_kind, kind):
    assert "setup_s" in reported_by_kind(kind)


def test_a_new_kind_is_one_driver_file_and_one_traffic_file(tmp_path):
    root = make_checkout(tmp_path, files={
        "chipbench/drivers/echo.py": ECHO_DRIVER,
        "chipbench/traffic/echo.json": json.dumps(ECHO_TRAFFIC)})
    assert "echo" in driver_kinds(root)
    assert check_kind(root, "echo") == {"echo_per_s", "setup_s"}
    assert bench.load_cell(root, "tiny_echo").traffic["n"] == 64


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_end_to_end_metric_is_one_its_driver_reports(
        reported_by_kind, workload):
    cell = bench.load_cell(REPO, workload)
    given = {m["name"] for m in cell.end_to_end}
    assert given <= reported_by_kind(cell.traffic["kind"]), workload


def test_sweep_cells_report_the_sweep_rate_and_setup_alone():
    cells = [bench.load_cell(REPO, w["name"]) for w in SPEC["workloads"]]
    sweeps = [c for c in cells if c.traffic["kind"] == "sweep"]
    assert sweeps
    for cell in sweeps:
        assert {m["name"] for m in cell.end_to_end} == {"sweep_req_per_s",
                                                        "setup_s"}


def test_configs_match_the_program():
    import jax

    from repro.core.profiles import paper_fleet, synthetic_fleet

    paper = fleets.tables(bench.load_cell(REPO, "paper_fig4_sweep").config)
    ref = paper_fleet()
    for k, v in (("T", ref.T), ("E", ref.E), ("mAP", ref.mAP),
                 ("floor_mw", ref.floor_mw)):
        np.testing.assert_array_equal(paper[k], np.asarray(v))
    city = json.loads((REPO / "chipbench" / "configs" / "city_fleet.json")
                      .read_text())
    gen = city["generator"]
    small = dict(city, generator=dict(gen, n_pairs=64))
    got = fleets.tables(small)
    want = synthetic_fleet(jax.random.PRNGKey(gen["key"]), 64)
    for k, v in (("T", want.T), ("E", want.E), ("mAP", want.mAP),
                 ("floor_mw", want.floor_mw)):
        np.testing.assert_array_equal(got[k], np.asarray(v))


def test_a_deployment_names_its_fleet(tmp_path):
    from repro.core.profiles import paper_fleet

    root = make_checkout(tmp_path)
    cell = bench.load_cell(root, "tiny_faults")
    assert cell.config["name"] == "paper_testbed_faults"
    assert cell.config["faults"] == TINY_FAULTS["faults"]
    assert "tables" not in json.loads(
        (root / "chipbench" / "configs" / "paper_testbed_faults.json")
        .read_text())
    got, ref = fleets.tables(cell.config), paper_fleet()
    for k, v in (("T", ref.T), ("E", ref.E), ("mAP", ref.mAP),
                 ("floor_mw", ref.floor_mw)):
        np.testing.assert_array_equal(got[k], np.asarray(v))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    (root / "chipbench" / "configs" / "nowhere.json").write_text(
        json.dumps({"name": "nowhere", "fleet": "no_such_fleet"}))
    configs["nowhere"] = dict(configs["tiny_city"], name="nowhere",
                              file="chipbench/configs/nowhere.json")
    with pytest.raises(KeyError, match="no_such_fleet"):
        bench.load_config(root, configs, "nowhere")


def test_a_new_cell_is_files_and_one_entry(tmp_path):
    root = make_checkout(tmp_path)
    b = root / "chipbench"
    (b / "configs" / "tiny_city2.json").write_text(json.dumps(
        dict(json.loads((b / "configs" / "tiny_city.json").read_text()),
             name="tiny_city2")))
    t = json.loads((b / "traffic" / "tiny_static.json").read_text())
    t.update(windows_per_call=2, n_streams=40)
    (b / "traffic" / "tiny_two.json").write_text(json.dumps(t))
    (b / "metrics" / "windows_seen.py").write_text(
        "def read(ctx):\n    return ctx.get('windows')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_city2", "source": "x",
                            "file": "chipbench/configs/tiny_city2.json",
                            "reduced": []})
    spec["workloads"].append({"name": "tiny_two", "config": "tiny_city2",
                              "traffic": "tiny_two", "chips": 1})
    for m in spec["end_to_end"]:
        if m["name"] == "plane_req_per_s":
            m["workloads"].append("tiny_two")
    spec["per_layer"].append({"name": "windows_seen", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "plane_req_per_s",
                              "workloads": ["tiny_two"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = bench.load_cell(root, "tiny_two")
    assert cell.config["name"] == "tiny_city2"
    assert cell.traffic["n_streams"] == 40
    assert [m["name"] for m in cell.per_layer] == ["windows_seen"]
    rc, res, _ = run_cell(root, "tiny_two", seconds=0.5, trace=1)
    assert rc == 0 and res["correct"]
    assert res["metrics"]["windows_seen"]["value"] % 2 == 0


def test_unknown_device_kind_raises():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_roofline_counts():
    ops, nbytes = roofline.moscore_hoisted_cost(4096, 5, 1000)
    assert ops == 20 * 4096 * 1024
    assert nbytes == 4 * (3 * 5 * 1024 + 2 * 1024 + 2 * 4096)
    pct, bound = roofline.roofline_pct(197e12, 0, 2.0, "TPU v5 lite")
    assert pct == pytest.approx(50.0) and bound == "compute"


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "chipbench" / "run.py"), "--workload",
         "paper_fig4_sweep", "--seed", "5", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
