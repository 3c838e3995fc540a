"""Find a cell, its configuration, its traffic mix and its metrics by name.

Everything a cell needs is a file of its own under ``benchmarks/chip``:

  cells/<workload>.json    engine settings and offered load of one cell
  configs/<config>.json    the model's sizes as run, with its source; the
                           program fields its sizes do not state
                           (``program.expect``) and, where the dense one
                           does not fit, its own reference
                           (``"reference": "references/<x>.py"``)
  traffic/<traffic>.json   parameters of the one traffic generator
  metrics/<metric>.py      a reader of one metric (``read(run)``)

``BENCHMARK.json`` at the repository root names the cells and metrics. A
later change adds a cell, a configuration, a mix or a metric by adding
files; no file here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]          # benchmarks/chip
REPO = ROOT.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(repo: Path = REPO) -> dict:
    return load_json(repo / "BENCHMARK.json")


class Cell:
    """One workload: its cell file, configuration and traffic mix."""

    def __init__(self, name: str, root: Path = ROOT, bench: dict | None = None):
        self.name = name
        self.root = root
        path = root / "cells" / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no cell file {path}")
        self.cell = load_json(path)
        self.config_name = self.cell["config"]
        self.traffic_name = self.cell["traffic"]
        if bench is not None:
            entry = {w["name"]: w for w in bench["workloads"]}.get(name)
            if entry is None:
                raise KeyError(f"{name} is not a workload of BENCHMARK.json")
            if (entry["config"], entry["traffic"]) != (self.config_name,
                                                       self.traffic_name):
                raise ValueError(f"{path} disagrees with BENCHMARK.json on "
                                 "its config or traffic")
            self.chips = entry["chips"]
        else:
            self.chips = self.cell.get("chips", 1)
        self.config = load_json(root / "configs" / f"{self.config_name}.json")
        self.traffic = load_json(root / "traffic" / f"{self.traffic_name}.json")
        self.engine = self.cell["engine"]
        self.load = self.cell.get("load", {})
        self.check = self.cell["check"]


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``workload`` reports: end-to-end ones
    untraced, per-layer ones traced; a metric with ``workloads`` only in
    the cells it lists."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def _load_module(name: str, path: Path):
    """The Python file at ``path``, loaded as a module named ``name``."""
    name = name.replace("/", "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return _load_module("chipbench_metric_" + name,
                        root / "metrics" / f"{name}.py").read


def load_reference(cell: Cell):
    """The plain reference the cell's configuration is compared against: the
    module its ``"reference"`` key names (a path under the benchmark's
    root, holding ``gaps`` and ``bucket`` as ``chipbench/reference.py``
    does), or ``chipbench/reference.py``."""
    rel = cell.config.get("reference")
    if rel is None:
        from chipbench import reference
        return reference
    path = (cell.root / rel).resolve()
    if not path.is_file() or not path.is_relative_to(cell.root.resolve()):
        raise FileNotFoundError(f"{cell.config_name}'s reference {rel} is "
                                f"not a file under {cell.root}")
    return _load_module("chipbench_reference_" + rel.removesuffix(".py"),
                        path)
