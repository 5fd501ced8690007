"""Useful work of the pooled expert FFN kernel (``paged_gmm``).

One call per MoE layer per step: the decode step's rows, or one prefill
chunk's (a whole prompt where prefill is monolithic).  The program routes
dropless by giving every expert capacity for every row of the step, so
the kernel computes ``E * T`` rows; only the ``T * k`` routed rows count
here, and only the pages of the experts that received a row, so a kernel
that skips padding or untouched experts can never read above its
roofline.  The program reports no routing counts by default, so the
number of experts touched is the expectation under uniform routing,
``E * (1 - (1 - k/E) ** T)``.
"""
from __future__ import annotations

from typing import List, Tuple


def experts(conf) -> int:
    return conf.get("num_experts", conf.get("n_routed_experts", 0))


def touched(E: int, k: int, tokens: int) -> float:
    """Expected distinct experts among ``tokens`` tokens routed top-``k``
    of ``E`` without replacement, uniformly."""
    return E * (1.0 - (1.0 - k / E) ** tokens)


def call(conf, tokens: int, wbytes: int = 2, abytes: int = 2
         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call over ``tokens`` tokens."""
    D, F = conf["hidden_size"], conf["moe_intermediate_size"]
    E, k = experts(conf), conf["num_experts_per_tok"]
    rows = tokens * k
    flops = 6.0 * D * F * rows                 # up, gate, down: 2 FLOPs/MAC
    page = 3.0 * D * F * wbytes                # wi, wg, wo of one expert
    act = 2.0 * rows * D * abytes              # each routed row in and out
    return flops, touched(E, k, tokens) * page + act


def moe_layers(conf) -> int:
    return conf["num_hidden_layers"] - conf.get("first_k_dense_replace", 0)


def calls(conf, tick) -> List[Tuple[float, float]]:
    """Every call one tick made: per MoE layer, the decode step and each
    prefill."""
    steps = ([len(tick.decode_ctx)] if tick.decode_ctx else []) \
        + [take for _, take in tick.prefill]
    return [call(conf, t) for t in steps for _ in range(moe_layers(conf))]
