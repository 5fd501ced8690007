"""What a cell is made of, read from files by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix; everything else about a cell lives in files
of its own under ``bench/``:

* ``configs/<config>.json``  the model as it is run (published keys, the
  serving shape, what was cut and what is not modelled);
* ``traffic/<mix>.json``     the length distributions of a traffic mix;
* ``cells/<cell>.json``      the cell's fixed offered rate and the limit of
  each number its correctness check compares.

A new cell, mix or configuration is new files plus entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

# what the program runs, whatever a configuration asks: these are fixed in
# its code (models/layers.py: apply_norm eps, rope_tables base and its
# interleaved pairs; models/moe.py: route renormalises the top-k weights)
PROGRAM_FIXED = {"rms_norm_eps": 1e-5, "rope_theta": 10000,
                 "norm_topk_prob": True, "rope_scaling": None}


def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # configs/<config>.json
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
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    per = [m for m in spec["per_layer"] if _applies(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(ROOT / conf["file"]),
        traffic=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
        params=_load(BENCH / "cells" / f"{name}.json"),
        per_layer=tuple(m["name"] for m in per),
        end_to_end=tuple(m["name"] for m in e2e),
        units={m["name"]: m["unit"] for m in e2e + per})


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file.

    Widths, depth and vocabulary come from the file; the registry entry
    named by ``registry`` supplies the architecture family.  A key the
    program cannot run as the file states raises: the file says what runs.
    """
    import dataclasses as dc

    from repro.configs import get_config
    for key, value in PROGRAM_FIXED.items():
        if key in conf and conf[key] != value:
            raise SystemExit(f"{conf['registry']}: {key}={conf[key]!r}, but "
                             f"the program runs {value!r}")
    base = get_config(conf["registry"])
    E = conf.get("num_experts", conf.get("n_routed_experts", 0))
    k = conf["num_experts_per_tok"]
    fields = dict(
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        num_experts=E, top_k=k,
        moe_d_ff=conf["moe_intermediate_size"],
        num_shared_experts=conf.get("n_shared_experts", 0),
        first_k_dense=conf.get("first_k_dense_replace", 0),
        dtype=conf["dtype"],
        # dropless routing: every token reaches its experts whatever the
        # batch (capacity_for gives C = T rows per expert)
        capacity_factor=E / k)
    if base.use_mla:
        fields.update(kv_lora_rank=conf["kv_lora_rank"],
                      q_lora_rank=conf["q_lora_rank"] or 0,
                      qk_nope_dim=conf["qk_nope_head_dim"],
                      qk_rope_dim=conf["qk_rope_head_dim"],
                      v_head_dim=conf["v_head_dim"], head_dim=0)
    else:
        fields.update(head_dim=conf["head_dim"])
    return dc.replace(base, **fields)
