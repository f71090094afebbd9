"""Finds a cell's configuration, traffic mix and per-layer metric readers
by the names ``BENCHMARK.json`` gives them.

A configuration is ``chipbench/configs/<config>.json``, a traffic mix is
``chipbench/traffic/<traffic>.json`` and a per-layer metric is
``chipbench/metrics/<metric>.py`` with a ``read(run)`` function, so a new
cell, mix or metric is a new file plus an entry, and no file that is
already there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Mapping, Optional

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, Callable]


def _load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def config(name: str, root: pathlib.Path = HERE) -> Dict:
    return _load_json(root / "configs" / f"{name}.json")


def traffic(name: str, root: pathlib.Path = HERE) -> Dict:
    return _load_json(root / "traffic" / f"{name}.json")


def reader(name: str, root: pathlib.Path = HERE) -> Callable:
    """``read`` of ``metrics/<name>.py``, loaded by path (metric names
    carry dots, which module names may not)."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: Mapping, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: Optional[Mapping] = None,
         root: pathlib.Path = HERE) -> Cell:
    """The cell ``name`` of ``bench`` (default: the repo's
    ``BENCHMARK.json``) with its files loaded."""
    if bench is None:
        bench = _load_json(REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]),
                config=config(w["config"], root),
                traffic=traffic(w["traffic"], root),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=per_layer,
                readers={m["name"]: reader(m["name"], root)
                         for m in per_layer})
