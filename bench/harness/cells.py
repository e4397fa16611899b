"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, population, source, reduced
    bench/traffic/<traffic>.json    how the window drives the entry
    bench/systems/<system>.py       the system under test for a family of
                                    configurations (``config["system"]``)
    bench/reference/<ref>.py        its plain reference
                                    (``config["reference"]``)
    bench/metrics/<metric>.py       one reader per metric

A later cell adds files and entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    end_to_end: bool
    workloads: Any = None     # None: every cell that reports what it moves
    moves: Any = None

    def applies_to(self, cell: "Cell", spec: Dict[str, Any]) -> bool:
        if self.workloads is not None:
            return cell.name in self.workloads
        if self.end_to_end:
            return True
        moved = _metric(spec, self.moves)
        return moved.applies_to(cell, spec)

    def reader(self):
        return importlib.import_module(f"bench.metrics.{self.name}")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    run_seconds: int

    def system(self):
        return importlib.import_module(
            f"bench.systems.{self.config['system']}")

    def reference(self):
        return importlib.import_module(
            f"bench.reference.{self.config['reference']}")


def load_spec(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _metric_of(entry: Dict[str, Any], end_to_end: bool) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"],
                  end_to_end=end_to_end, workloads=entry.get("workloads"),
                  moves=entry.get("moves"))


def _metric(spec: Dict[str, Any], name: str) -> Metric:
    for entry in spec["end_to_end"]:
        if entry["name"] == name:
            return _metric_of(entry, True)
    raise KeyError(f"no end-to-end metric {name!r} in BENCHMARK.json")


def resolve(name: str, spec: Dict[str, Any] = None,
            root: str = ROOT) -> Cell:
    """The cell called ``name``, with its configuration and traffic files
    loaded and its metrics listed.  An unknown name raises ``KeyError``."""
    spec = spec if spec is not None else load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=[], per_layer=[],
                run_seconds=int(spec["run_seconds"]))
    e2e = [_metric_of(m, True) for m in spec["end_to_end"]]
    layer = [_metric_of(m, False) for m in spec["per_layer"]]
    return dataclasses.replace(
        cell,
        end_to_end=[m for m in e2e if m.applies_to(cell, spec)],
        per_layer=[m for m in layer if m.applies_to(cell, spec)])
