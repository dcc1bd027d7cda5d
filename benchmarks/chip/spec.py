"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (one entry of ``workloads``) names a configuration, whose file
``BENCHMARK.json`` gives, and a traffic mix, found at
``traffic/<name>.json``. Each per-layer metric is read by
``metrics/<name>.py``. Adding a cell, a mix or a metric adds files and
entries here and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def traffic_path(name: str) -> Path:
    return HERE / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return HERE / "metrics" / f"{name}.py"


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(bench: Dict, workload: str, root: Path = ROOT) -> Cell:
    """Resolve one ``workloads`` entry; KeyError names what is missing."""
    w = {c["name"]: c for c in bench["workloads"]}.get(workload)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"])) as f:
        traffic = json.load(f)
    return Cell(name=w["name"], chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, w["name"])],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, w["name"])])


def load_module(path: Path, name: str):
    """The Python file at ``path``, imported as module ``name``: the
    benchmark's files named by data, whose names may hold dots."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    """The ``read(run)`` function of ``metrics/<name>.py``: it returns the
    metric's value, or None where the run gave it nothing to read."""
    return load_module(metric_path(name),
                       f"benchmarks.chip.metrics.{name}").read
