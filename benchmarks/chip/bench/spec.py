"""The benchmark's data, found by name.

``BENCHMARK.json`` at the root of the checkout names the cells; each
configuration is the file it names, whose ``"reference"`` names its
architecture module ``archs/<name>.py``; each traffic mix is
``traffic/<name>.json``, each cell's limits for ``correct`` are
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<name>.py``.  A new architecture, cell or metric is new files,
and no edit.
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


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(name: str, here: Path = HERE):
    """The architecture module ``archs/<name>.py``: the reference's
    leaves, layers and FLOP count, and the check of the program's
    configuration against the file."""
    return load_module(here / "archs" / f"{name}.py", f"_arch_{name}")


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
        self.arch = load_arch(self.model["reference"], here)
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
        return load_module(self.here / "metrics" / f"{metric}.py",
                           f"_metric_{metric.replace('.', '_')}").read
