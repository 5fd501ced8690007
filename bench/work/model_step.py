"""Useful FLOPs of the whole model: what a served token costs.

The configuration's forward pass counted as the architecture defines it:
every projection at 2 FLOPs per multiply-add, attention scores and values
over the positions a token attends, the routed experts a token is sent to
(not the capacity the program pads), shared experts, the router, and the
LM head once per decoded token (prompt positions produce no logits that are
used; the one that yields a request's first token is left out too).  Latent
attention is counted in its expanded form.
"""
from __future__ import annotations


def _attention(conf, ctx: float) -> float:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    if "kv_lora_rank" in conf:
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
        proj = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) \
            + H * dv * D
        return 2.0 * proj + 2.0 * ctx * H * (dn + dr + dv)
    KVH, hd = conf["num_key_value_heads"], conf["head_dim"]
    proj = D * H * hd + 2 * D * KVH * hd + H * hd * D
    return 2.0 * proj + 4.0 * ctx * H * hd


def _ffn(conf, dense: bool) -> float:
    D = conf["hidden_size"]
    if dense:
        return 6.0 * D * conf["intermediate_size"]
    F = conf["moe_intermediate_size"]
    E = conf.get("num_experts", conf.get("n_routed_experts", 0))
    k = conf["num_experts_per_tok"]
    shared = conf.get("n_shared_experts", 0)
    return 2.0 * D * E + 6.0 * D * F * (k + shared)


def token(conf, ctx: float, logits: bool) -> float:
    """FLOPs of one token attending ``ctx`` positions (itself included)."""
    nk = conf.get("first_k_dense_replace", 0)
    L = conf["num_hidden_layers"]
    f = L * _attention(conf, ctx) + nk * _ffn(conf, True) \
        + (L - nk) * _ffn(conf, False)
    if logits:
        f += 2.0 * conf["hidden_size"] * conf["vocab_size"]
    return f


def tick(conf, t) -> float:
    """FLOPs of one tick: its decoded rows and its prefill."""
    f = sum(token(conf, c, True) for c in t.decode_ctx)
    for start, take in t.prefill:
        # attention is linear in the context: a chunk's positions attend
        # start + (take + 1) / 2 positions on average
        f += take * token(conf, start + (take + 1) / 2.0, False)
    return f
