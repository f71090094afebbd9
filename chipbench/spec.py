"""Finds a cell's configuration, traffic mix and per-layer metric readers
by the names ``BENCHMARK.json`` gives them.

A configuration is ``chipbench/configs/<config>.json``, a traffic mix is
``chipbench/traffic/<traffic>.json`` and a per-layer metric is
``chipbench/metrics/<metric>.py`` with a ``read(run)`` function, so a new
cell, mix or metric is a new file plus an entry, and no file that is
already there changes.

What depends on the model's architecture, a configuration names by the
path of a module, from the root of the checkout:

- ``check.reference``: its plain reference, whose ``score`` decides
  ``correct`` (the contract is ``chipbench/reference.py``'s docstring);
  by default ``chipbench/reference.py``, the dense decoder.
- ``counts``: its operation and byte counts, whose ``slice_flops`` and
  ``decode_slice_min_bytes`` the ``mfu`` and ``decode_roofline`` readers
  call (the contract is ``chipbench/flops.py``'s docstring); by default
  ``chipbench/flops.py``, the dense decoder.

``cell`` loads both before anything runs, so a configuration that names
a file that is not there fails at once and not after the window.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import types
from typing import Callable, Dict, List, Mapping, Optional

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
DEFAULT_REFERENCE = "chipbench/reference.py"
DEFAULT_COUNTS = "chipbench/flops.py"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    readers: Dict[str, Callable]
    #: the configuration's plain reference (``check.reference``)
    reference: types.ModuleType
    #: the configuration's operation and byte counts (``counts``)
    counts: types.ModuleType


def _load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def config(name: str, root: pathlib.Path = HERE) -> Dict:
    return _load_json(root / "configs" / f"{name}.json")


def traffic(name: str, root: pathlib.Path = HERE) -> Dict:
    return _load_json(root / "traffic" / f"{name}.json")


def _load(path: pathlib.Path, name: str) -> types.ModuleType:
    """The module in the file ``path``, loaded by path as ``name``."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_").replace("/", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: pathlib.Path = HERE) -> Callable:
    """``read`` of ``metrics/<name>.py``, loaded by path (metric names
    carry dots, which module names may not)."""
    return _load(root / "metrics" / f"{name}.py",
                 "chipbench_metric_" + name).read


def _module(conf: Mapping, key: str, rel: str) -> types.ModuleType:
    """The module at ``rel``, a path from the root of the checkout that
    the configuration ``conf`` gives by ``key``.  A path that leads out
    of the checkout or to no file is a ``KeyError`` that names the key
    and the path."""
    path = (REPO / rel).resolve()
    if REPO not in path.parents or not path.is_file():
        raise KeyError(f"configuration {conf.get('name')!r}: {key} names "
                       f"{rel!r}, which is no file in the checkout")
    return _load(path, "chipbench_module_" + rel.removesuffix(".py"))


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
    conf = config(w["config"], root)
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=conf,
                traffic=traffic(w["traffic"], root),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=per_layer,
                readers={m["name"]: reader(m["name"], root)
                         for m in per_layer},
                reference=_module(conf, "check.reference",
                                  conf["check"].get("reference",
                                                    DEFAULT_REFERENCE)),
                counts=_module(conf, "counts",
                               conf.get("counts", DEFAULT_COUNTS)))
