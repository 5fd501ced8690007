"""What a cell is made of, read from files by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix; everything else about a cell lives in files
of its own under ``bench/``, which the harness finds by name:

* ``configs/<config>.json``  the model as it is run (published keys, the
  serving shape, what was cut and what is not modelled), and ``"arch"``,
  the name of its architecture file;
* ``arch/<arch>.py``         the architecture: how the program's
  ``ModelConfig`` follows from the file, the weights in the program's
  layout, the plain reference's forward pass and the FLOPs of a token;
* ``traffic/<mix>.json``     the length distributions of a traffic mix;
* ``cells/<cell>.json``      the cell's fixed offered rate and the limit of
  each number its correctness check compares;
* ``metrics/<metric>.py``    the reader of one metric;
* ``work/<kernel>.py``       the FLOPs and bytes of one kernel's calls.

A new configuration brings ``configs/<name>.json`` with its ``"arch"``;
``arch/<arch>.py`` only where no existing file computes its block;
``cells/<cell>.json``; ``metrics/`` and ``work/`` files for kernels no
existing reader counts; and appended ``configs``, ``workloads`` and
``per_layer`` entries in ``BENCHMARK.json``.  Nothing here changes, and a
metric without a ``workloads`` list is read in every cell, new ones too.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def plugin(kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` as a module."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    s = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    sys.modules[mod_name] = mod
    return mod


def arch(conf: Dict[str, Any]) -> ModuleType:
    """The architecture file a configuration names."""
    return plugin("arch", conf["arch"])


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # configs/<config>.json
    arch: ModuleType             # arch/<config's "arch">.py
    traffic: Dict[str, Any]      # traffic/<mix>.json
    params: Dict[str, Any]       # cells/<cell>.json
    per_layer: tuple             # names of the per-layer metrics it reports
    end_to_end: tuple            # names of the end-to-end metrics it reports
    units: Dict[str, str]        # metric name -> unit


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = _load(benchmark)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; choose from "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = _load(ROOT / conf["file"])
    if "arch" not in config:
        raise SystemExit(f"{conf['file']}: no \"arch\": name the "
                         "architecture file under bench/arch/ that "
                         "computes this configuration")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    per = [m for m in spec["per_layer"] if _applies(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, arch=arch(config),
        traffic=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
        params=_load(BENCH / "cells" / f"{name}.json"),
        per_layer=tuple(m["name"] for m in per),
        end_to_end=tuple(m["name"] for m in e2e),
        units={m["name"]: m["unit"] for m in e2e + per})
