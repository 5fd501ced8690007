"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds:

  compute    = HLO_FLOPs   / (chips * peak_FLOP/s)
  memory     = HLO_bytes   / (chips * HBM_bw)
  collective = coll_bytes  / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``;
collective bytes are parsed out of the optimized HLO text (sum of the output
buffer sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops), since XLA's cost model does not expose them.

Per-chip peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``;
a kind missing from the table raises rather than borrowing another chip's
numbers.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity
    ici_bw: float         # bytes/s per ICI link
    source: str


#: keyed by ``device_kind`` as JAX reports it
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip (taken "
               "as 4 links of 50 GB/s)"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       " add them to analysis.roofline.PEAKS with their "
                       "source") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_ARRAY_RE = re.compile(r"(pred|[suf]\d+|bf16|f16|f32|f64|c64|c128)\[([\d,]*)\]")


def _array_bytes(text: str) -> int:
    """Sum sizes of all array literals in an HLO type string."""
    total = 0
    for dt, dims in _ARRAY_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind output bytes of communication ops in optimized HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    start_re = re.compile(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+(" +
        "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")
    seen_done = set()
    for line in hlo_text.splitlines():
        m = start_re.match(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        # avoid double counting async pairs: count -start, skip -done;
        # count sync form normally
        if f"{kind}-done(" in line:
            continue
        out[kind] += _array_bytes(type_str)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # total HLO flops (whole program)
    hbm_bytes: float             # total bytes accessed
    coll_bytes: Dict[str, int]   # per collective kind (global)
    chips: int
    device_kind: str             # key into PEAKS
    model_flops: float = 0.0     # 6*N*D (analytic)

    @property
    def peaks(self) -> Peaks:
        return peaks_for(self.device_kind)

    @property
    def total_coll_bytes(self) -> float:
        return float(sum(self.coll_bytes.values()))

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * self.peaks.flops)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.total_coll_bytes / (self.chips * self.peaks.ici_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        if not self.flops:
            return float("nan")
        return self.model_flops / self.flops

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_bytes_total": self.total_coll_bytes,
            "chips": self.chips, "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def from_compiled(compiled, chips: int, device_kind: str,
                  model_flops: float = 0.0,
                  hlo_text: Optional[str] = None) -> Roofline:
    """Roofline terms from a compiled executable.

    Primary source is the loop-aware HLO analyzer (hlo_costs.analyze) —
    XLA:CPU's cost_analysis counts while bodies once, which under-reports
    scanned-layer models by ~num_layers x.  Totals below are global
    (per-device analyzer output x chips).
    """
    from repro.analysis import hlo_costs
    text = hlo_text if hlo_text is not None else compiled.as_text()
    costs = hlo_costs.analyze(text)
    flops = costs.flops * chips
    hbm = costs.bytes_accessed * chips
    coll = {k: int(v * chips) for k, v in costs.coll_bytes.items()}
    return Roofline(flops=flops, hbm_bytes=hbm, coll_bytes=coll, chips=chips,
                    device_kind=device_kind, model_flops=model_flops)


def model_flops_for(mcfg, shape, kind: str) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*tokens for train, 2*N_active*tokens
    for inference forward passes."""
    n_active = mcfg.param_count(active_only=True)
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
