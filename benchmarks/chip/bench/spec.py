"""The benchmark's data, found by name.

``BENCHMARK.json`` at the root of the checkout names the cells; each
configuration is the file it names, each traffic mix is
``traffic/<name>.json``, each cell's limits for ``correct`` are
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<name>.py``.  A new cell or metric is new files, and no edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]          # the benchmark's directory
ROOT = HERE.parents[1]                              # the checkout


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, name: str, root: Path = ROOT, here: Path = HERE):
        self.bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.model = load_json(root / self.config_entry["file"])
        self.traffic_name = self.entry["traffic"]
        self.mix = load_json(here / "traffic" / f"{self.traffic_name}.json")
        self.limits = load_json(here / "limits" / f"{name}.json")
        self.here = here

    def metrics(self, group: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def reader(self, metric: str):
        """``read(ctx) -> float | None`` of a per-layer metric."""
        path = self.here / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
