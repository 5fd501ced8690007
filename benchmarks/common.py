"""Shared glue for the paper-figure benchmarks."""
from __future__ import annotations

import io
import os
import sys
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from repro.configs import get_config
from repro.core.costmodel import DEFAULT_HW, plan_cost
from repro.core.scaling_plan import (Op, STRATEGIES, placement, plan_elastic,
                                     plan_elastic_min_move)
from repro.core.topology import ElasticConfig, kv_cache_bytes, model_tensors


def cpu_child_env(ndev: int = 8) -> Dict[str, str]:
    """Environment for a benchmark's subprocess arm: ``ndev`` virtual CPU
    devices, always on the CPU backend — on a machine with a TPU the child
    must never try to take the chip this harness's process may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return env


PAPER_MODELS = ["deepseek-v2-lite-16b", "qwen3-30b-a3b", "deepseek-v3"]
TP_OF = {"deepseek-v2-lite-16b": 2, "qwen3-30b-a3b": 2, "deepseek-v3": 2}

STRATEGY_LABELS = {
    "elastic": "ElasticMoE (ours)",
    "cold_restart": "Vertical (Cold Restart)",
    "extravagant": "Vertical (Extravagant)",
    "colocated": "Vertical (Colocated)",
    "horizontal": "Horizontal (Replica)",
}


def tensors_for(name: str, tp: int, kv_batch: int = 8, kv_len: int = 4096,
                kv_dtype: Optional[str] = None,
                expert_dtype: Optional[str] = None):
    mcfg = get_config(name)
    kvb = kv_cache_bytes(mcfg, kv_batch, kv_len, kv_dtype=kv_dtype)
    return mcfg, model_tensors(mcfg, tp, kv_bytes_per_replica=kvb,
                               expert_dtype=expert_dtype)


def cfg_of(n: int, tp: int, base: int = 0) -> ElasticConfig:
    return ElasticConfig(dp=n // tp, tp=tp,
                         devices=tuple(range(base, base + n)))


def scale_cost(name: str, n_old: int, n_new: int, strategy: str,
               preinit: bool = True, paged: bool = True,
               kv_dtype: Optional[str] = None,
               expert_dtype: Optional[str] = None, **flags):
    """Plan + cost for one transition under one strategy."""
    tp = TP_OF.get(name, 2)
    mcfg, tensors = tensors_for(name, tp, kv_dtype=kv_dtype,
                                expert_dtype=expert_dtype)
    old = cfg_of(n_old, tp)
    if strategy in ("extravagant", "horizontal"):
        new = cfg_of(n_new, tp, base=n_old)
    else:
        new = cfg_of(n_new, tp)
    if strategy == "elastic" and paged and mcfg.is_moe:
        plan = plan_elastic_min_move(tensors, old, new, mcfg)
    else:
        plan = STRATEGIES[strategy](tensors, old, new)
    resident = {d: sum(s.values())
                for d, s in placement(tensors, old).items()}
    return plan, plan_cost(plan, preinit=preinit, strategy=strategy,
                           resident_bytes_per_device=resident, **flags)


def feasible(strategy: str, n_old: int, n_new: int, total_devices: int = 384):
    if strategy == "horizontal":
        return n_new == 2 * n_old and n_old + n_new <= total_devices
    if strategy == "extravagant":
        return n_old + n_new <= total_devices
    return True


class Table:
    def __init__(self, name: str, cols: List[str]):
        self.name = name
        self.cols = cols
        self.rows: List[List] = []

    def add(self, *vals):
        self.rows.append(list(vals))

    def show(self, file=sys.stdout):
        print(f"\n## {self.name}", file=file)
        widths = [max(len(str(c)), *(len(_fmt(r[i])) for r in self.rows))
                  if self.rows else len(str(c))
                  for i, c in enumerate(self.cols)]
        print("  ".join(str(c).ljust(w) for c, w in zip(self.cols, widths)),
              file=file)
        for r in self.rows:
            print("  ".join(_fmt(v).ljust(w) for v, w in zip(r, widths)),
                  file=file)

    def csv_rows(self):
        for r in self.rows:
            yield f"{self.name}," + ",".join(_fmt(v) for v in r)


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.3f}"
    return str(v)
