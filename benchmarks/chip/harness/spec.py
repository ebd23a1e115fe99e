"""Resolve a workload of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration and a traffic mix. The configuration's JSON
file is the entry's ``file``; its ``mode`` picks the general driver
``drivers/<mode>.py`` and its ``reference`` the plain reference module.
The traffic mix is ``traffic/<traffic>.json``. Every metric, end-to-end or
per-layer, is read by ``metrics/<metric name>.py``. Adding a cell,
configuration or metric therefore means adding files and entries, never
editing this module.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import List

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                             # checkout root


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_module(path: Path) -> ModuleType:
    """Import one file by path (its name may hold dots or dashes); a file
    is imported once per process."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    name = "bench_" + re.sub(r"\W", "_", str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: ModuleType
    per_layer: bool


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: Path = BENCH_DIR

    def driver(self) -> ModuleType:
        return load_module(self.bench_dir / "drivers"
                           / f"{self.config['mode']}.py")

    def reference(self) -> ModuleType:
        return load_module(self.bench_dir / self.config["reference"])


def _reports(entry: dict, cell: str, e2e_names: List[str]) -> bool:
    """Whether a metric entry is reported in ``cell``: its ``workloads``
    list when given; otherwise every cell (end-to-end) or every cell that
    reports the end-to-end metric it moves (per-layer)."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    if "moves" in entry:
        return entry["moves"] in e2e_names
    return True


def resolve(workload: str, root: Path = ROOT,
            bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files,
    found under ``bench_dir``, loaded. Raises ``SpecError`` when anything it
    names is missing."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")

    def metric(entry: dict, per_layer: bool) -> Metric:
        return Metric(entry["name"], entry["unit"], entry["better"],
                      entry["source"],
                      load_module(bench_dir / "metrics"
                                  / f"{entry['name']}.py"),
                      per_layer)

    e2e = [metric(m, False) for m in bench["end_to_end"]
           if _reports(m, workload, [])]
    names = [m.name for m in e2e]
    layer = [metric(m, True) for m in bench["per_layer"]
             if _reports(m, workload, names)]
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"],
                config, traffic, e2e, layer, bench_dir)
