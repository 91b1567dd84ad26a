"""Find a cell's files by the names in ``BENCHMARK.json``.

* configuration: the ``file`` its ``configs`` entry names; its
  ``generator`` key names ``bench/gen/<generator>.py``;
* traffic mix: ``bench/traffic/<traffic>.json``; its ``driver`` key names
  ``bench/drivers/<driver>.py``;
* per-layer metric: ``bench/metrics/<name>.py``, whose ``read(readings)``
  returns the value or None;
* limits of the numbers that decide ``correct``: ``bench/limits/<cell>.json``.

A later cell, mix or metric is new files and new entries; nothing here
changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]  # the per-layer metrics this cell reports
    limits: Dict[str, float]
    root: Path

    def module(self, kind: str, name: str):
        return load_module(self.root / "bench" / kind / f"{name}.py")

    def generator(self):
        return self.module("gen", self.config["generator"])

    def driver(self):
        return self.module("drivers", self.traffic["driver"])


def load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    name = "bench_" + f"{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits" / f"{name}.json").read_text())["limits"]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=c["name"],
        config=config,
        traffic_name=w["traffic"],
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        limits=limits,
        root=root,
    )
