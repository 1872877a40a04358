"""Finds a cell's files by name: nothing here lists a configuration, a
traffic mix, a study module or a per-layer metric.

* ``BENCHMARK.json`` (at the checkout root) names the cell's configuration
  and traffic, and the metrics with the cells they apply to;
* ``bench/configs/<config>.json`` is the deployment;
* ``bench/traffic/<traffic>.json`` is the traffic mix; its ``kind`` names
  the study module ``bench/drivers/<kind>.py``;
* ``bench/layer_metrics/<metric>.py`` reads one per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a Python file by path (drivers and metric readers)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    bench_dir: str = BENCH_DIR

    @property
    def chips(self) -> int:
        return int(self.entry.get("chips", 1))

    def driver(self):
        kind = self.traffic["kind"]
        return load_module(os.path.join(self.bench_dir, "drivers", f"{kind}.py"),
                           f"bench_driver_{kind}")

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "layer_metrics", f"{metric}.py"),
                           f"bench_metric_{metric.replace('.', '_')}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    """The cell named ``name`` in ``<root>/BENCHMARK.json`` with its files
    under ``<root>/bench``."""
    bench_dir = os.path.join(root, "bench")
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    config = _load_json(os.path.join(bench_dir, "configs", f"{entry['config']}.json"))
    traffic = _load_json(os.path.join(bench_dir, "traffic", f"{entry['traffic']}.json"))
    return Cell(name=name, entry=entry, config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                bench_dir=bench_dir)


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]
