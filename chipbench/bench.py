"""Load the benchmark's pieces by name: ``BENCHMARK.json`` at the root of
the checkout, one JSON file per configuration and per traffic mix, one
driver module per traffic kind and one reader module per per-layer
metric. A later change adds a cell by adding files and entries; nothing
here has to change for it.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

#: the benchmark's directory, relative to the root of a checkout
BENCH_DIR = "chipbench"


@dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list     # the cell's end-to-end metric entries
    per_layer: list      # the cell's per-layer metric entries
    root: Path           # the checkout the cell was loaded from


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file missing: {path}")
    return json.loads(path.read_text())


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark as a module of its own (metric
    names hold dots, so they are loaded by path, not by import name)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark module missing: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(root: Path, configs: dict, name: str) -> dict:
    """The configuration ``name`` of ``configs`` (``BENCHMARK.json``'s
    entries by name). One that names another's fleet (``"fleet"``) gets
    that configuration, loaded, in the name's place."""
    if name not in configs:
        raise KeyError(f"no configuration {name!r}; known: "
                       f"{sorted(configs)}")
    config = _load_json(root / configs[name]["file"])
    if "fleet" in config:
        fleet = load_config(root, configs, config["fleet"])
        if "fleet" in fleet:
            raise ValueError(f"configuration {name!r} names the fleet of "
                             f"{config['fleet']!r}, which names another")
        config["fleet"] = fleet
    return config


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its
    configuration, traffic mix and metric entries."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_config(root, configs, w["config"])
    traffic = _load_json(root / BENCH_DIR / "traffic"
                         / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=Path(root))


def driver(cell: Cell) -> ModuleType:
    """The driver module of the cell's traffic kind."""
    return load_module(cell.root / BENCH_DIR / "drivers"
                       / f"{cell.traffic['kind']}.py")


def metric_reader(cell: Cell, name: str) -> ModuleType:
    """The reader module of one per-layer metric."""
    return load_module(cell.root / BENCH_DIR / "metrics" / f"{name}.py")
