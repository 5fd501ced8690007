"""Useful FLOPs of the whole model: what a served token costs.

Each token counted by its configuration's architecture file (``token`` in
``bench/arch/<arch>.py``), the LM head once per decoded token: prompt
positions produce no logits that are used (the one that yields a
request's first token is left out too).
"""
from __future__ import annotations

from harness import spec


def tick(conf, t) -> float:
    """FLOPs of one tick: its decoded rows and its prefill."""
    token = spec.arch(conf).token
    f = sum(token(conf, c, True) for c in t.decode_ctx)
    for start, take in t.prefill:
        # attention is linear in the context: a chunk's positions attend
        # start + (take + 1) / 2 positions on average
        f += take * token(conf, start + (take + 1) / 2.0, False)
    return f
