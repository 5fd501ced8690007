"""Useful work of the paged attention kernel (``paged_attention``).

One call per layer per step.  A decode call reads the K and V of each
running sequence's live positions and its query; a chunked-prefill call
reads the context's K and V once and computes the causal scores of the
chunk's queries.  Positions beyond a sequence's length, blocks of other
sequences and tiles re-read for each query tile do not count.
"""
from __future__ import annotations

from typing import List, Tuple


def _dims(conf):
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"])


def decode(conf, ctx: List[int], abytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode call over rows with ``ctx`` positions."""
    H, KVH, hd = _dims(conf)
    pos = float(sum(ctx))
    flops = 4.0 * pos * H * hd                 # q.k and p.v, 2 FLOPs/MAC
    kv = 2.0 * pos * KVH * hd * abytes
    q_out = 2.0 * len(ctx) * H * hd * abytes
    return flops, kv + q_out


def chunk(conf, start: int, take: int, abytes: int = 2
          ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk: ``take`` queries after
    ``start`` cached positions, causal."""
    H, KVH, hd = _dims(conf)
    pairs = take * start + take * (take + 1) / 2.0
    flops = 4.0 * pairs * H * hd
    kv = 2.0 * (start + take) * KVH * hd * abytes
    q_out = 2.0 * take * H * hd * abytes
    return flops, kv + q_out


def calls(conf, tick) -> List[Tuple[float, float]]:
    L = conf["num_hidden_layers"]
    out = [decode(conf, tick.decode_ctx)] * L if tick.decode_ctx else []
    for start, take in tick.prefill:
        out += [chunk(conf, start, take)] * L
    return out
