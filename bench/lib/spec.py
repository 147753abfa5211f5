"""BENCHMARK.json and the files it names, found by name.

A cell (one entry of ``workloads``) resolves to

* its configuration: ``configs[i].file``, a JSON file of sizes;
* its traffic mix: ``bench/traffic/<traffic>.json``;
* its per-layer metrics: ``bench/metrics/<metric>.py``, each a module with
  ``read(run) -> float | None``.

Adding a cell is adding those files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "bench"

def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SystemExit(f"no BENCHMARK.json at {path}")
    return json.loads(path.read_text())


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "traffic" / f"{name}.json"


def metric_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "metrics" / f"{name}.py"


def load_module(name: str, root: Path = ROOT):
    """One per-layer metric's own file, as a module with ``read(run)``."""
    path = metric_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end_for(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: dict, cell: str) -> list:
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list


def resolve(bench: dict, workload: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((Path(root) / cfg_entry["file"]).read_text())
    traffic = json.loads(traffic_path(w["traffic"], root).read_text())
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=end_to_end_for(bench, workload),
                per_layer=per_layer_for(bench, workload))
