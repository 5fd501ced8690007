"""Softmax-routed MoE decoder: the architecture of ``qwen3_30b_a3b``.

Leading dense layers (``first_k_dense_replace``), then MoE layers.
Attention is grouped-query, or multi-head latent attention (MLA) with a
full-rank q and a decoupled rotary key where the file states
``kv_lora_rank``.  RMSNorm with ``rms_norm_eps``; rotary embedding with
base ``rope_theta`` on interleaved pairs (the GPT-J layout: the same
function as the rotate-half layout up to a fixed permutation of the q/k
projection columns).  SwiGLU experts behind a softmax router whose top-k
weights are renormalised (``norm_topk_prob: true``); shared experts added
to every token.

``model_config``: the program's ``ModelConfig``; ``params`` / ``layer``:
the weights, the whole tree or a layer alone; ``hidden`` / ``head``: the
plain reference; ``token``: the useful FLOPs of one token.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from harness import weights
from harness.reference import MATMUL, causal_softmax_mix, f32, mm, rms, rope

# what the program runs, whatever a configuration asks: these are fixed in
# its code (models/layers.py: apply_norm eps, rope_tables base and its
# interleaved pairs; models/moe.py: route renormalises the top-k weights)
PROGRAM_FIXED = {"rms_norm_eps": 1e-5, "rope_theta": 10000,
                 "norm_topk_prob": True, "rope_scaling": None}


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
    E = _experts(conf)
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
        first_k_dense=n_prefix(conf),
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


def _experts(conf) -> int:
    return conf.get("num_experts", conf.get("n_routed_experts", 0))


def n_prefix(conf) -> int:
    return conf.get("first_k_dense_replace", 0)


# Weights.  Scales: ``N(0, 1/fan_in)`` for projections, ``N(0, 0.02^2)``
# for the embedding, ones for norm scales; the router is float32, as the
# program keeps it.

# part ids: one key family per stacked layer kind and for the top level
_TOP, _PREFIX, _BLOCK = 0, 1, 2
_LEAVES = ("embed", "lm_head", "q", "k", "v", "o", "kv_down", "k_up", "v_up",
           "router", "wi", "wg", "wo", "up", "gate", "down", "sh_up",
           "sh_gate", "sh_down")


def _normal(key, name, shape, scale, dtype):
    return weights.normal(key, _LEAVES.index(name), shape, scale, dtype)


def _lin(key, name, d_in, d_out, dtype):
    return weights.lin(key, _LEAVES.index(name), d_in, d_out, dtype)


def _attention_weights(conf, key, dtype) -> Dict[str, Any]:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    if "kv_lora_rank" in conf:       # latent attention (MLA), full-rank q
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
        return {"q": _lin(key, "q", D, H * (dn + dr), dtype),
                "kv_down": _lin(key, "kv_down", D, r + dr, dtype),
                "kv_norm": weights.ones(r, dtype),
                "k_up": _lin(key, "k_up", r, H * dn, dtype),
                "v_up": _lin(key, "v_up", r, H * dv, dtype),
                "o": _lin(key, "o", H * dv, D, dtype)}
    KVH, hd = conf["num_key_value_heads"], conf["head_dim"]
    return {"q": _lin(key, "q", D, H * hd, dtype),
            "k": _lin(key, "k", D, KVH * hd, dtype),
            "v": _lin(key, "v", D, KVH * hd, dtype),
            "o": _lin(key, "o", H * hd, D, dtype)}


def _mlp_weights(key, names, D, F, dtype):
    up, gate, down = names
    return {"up": _lin(key, up, D, F, dtype),
            "down": _lin(key, down, F, D, dtype),
            "gate": _lin(key, gate, D, F, dtype)}


def _moe_weights(conf, key, dtype) -> Dict[str, Any]:
    D, F, E = conf["hidden_size"], conf["moe_intermediate_size"], \
        _experts(conf)
    p = {"router": _lin(key, "router", D, E, jnp.float32),
         "wi": _normal(key, "wi", (E, D, F), 1 / math.sqrt(D), dtype),
         "wg": _normal(key, "wg", (E, D, F), 1 / math.sqrt(D), dtype),
         "wo": _normal(key, "wo", (E, F, D), 1 / math.sqrt(F), dtype)}
    shared = conf.get("n_shared_experts", 0)
    if shared:
        p["shared"] = _mlp_weights(key, ("sh_up", "sh_gate", "sh_down"), D,
                                   F * shared, dtype)
    return p


def layer(conf, key, kind: str, i: int) -> Dict[str, Any]:
    """One layer's leaves: ``kind`` is ``"prefix"`` (a leading dense layer)
    or ``"block"`` (a MoE layer), ``i`` its index among its kind."""
    dtype = jnp.dtype(conf["dtype"])
    D = conf["hidden_size"]
    part = _PREFIX if kind == "prefix" else _BLOCK
    k = jax.random.fold_in(jax.random.fold_in(key, part), i)
    p = {"ln1": weights.ones(D, dtype), "ln2": weights.ones(D, dtype),
         "attn": _attention_weights(conf, k, dtype)}
    if kind == "prefix":
        p["mlp"] = _mlp_weights(k, ("up", "gate", "down"), D,
                                conf["intermediate_size"], dtype)
    else:
        p["moe"] = _moe_weights(conf, k, dtype)
    return p


def top(conf, key) -> Dict[str, Any]:
    dtype = jnp.dtype(conf["dtype"])
    D, V = conf["hidden_size"], conf["vocab_size"]
    k = jax.random.fold_in(key, _TOP)
    return {"final_norm": weights.ones(D, dtype),
            "embed": _normal(k, "embed", (V, D), 0.02, dtype),
            "lm_head": _lin(k, "lm_head", D, V, dtype)}


def params(conf, key) -> Dict[str, Any]:
    """The whole tree in the program's layout (``models/model.init_params``):
    leading dense layers as a list, MoE layers stacked on a leading axis."""
    p = top(conf, key)
    nk = n_prefix(conf)
    if nk:
        p["dense_prefix"] = [layer(conf, key, "prefix", i) for i in range(nk)]
    blocks = [layer(conf, key, "block", i)
              for i in range(conf["num_hidden_layers"] - nk)]
    p["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    return p


# Reference.  Full causal attention over each whole sequence, and every
# routed expert computed for every token and weighted by its renormalised
# top-k gate (zero where it is not routed).

def _attention(conf, w, h, mm_):
    """One sequence: h [S, D] (normed) -> [S, D]."""
    S = h.shape[0]
    H = conf["num_attention_heads"]
    pos = jnp.arange(S)
    base = float(conf["rope_theta"])
    if "kv_lora_rank" in conf:
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
        q = mm_(h, w["q"]["w"]).reshape(S, H, dn + dr)
        ckr = mm_(h, w["kv_down"]["w"])
        c = rms(ckr[:, :r], w["kv_norm"]["scale"], conf["rms_norm_eps"])
        k_rope = rope(ckr[:, None, r:], pos, base)[:, 0]      # [S, dr]
        q_rope = rope(q[..., dn:], pos, base)
        k_nope = mm_(c, w["k_up"]["w"]).reshape(S, H, dn)
        v = mm_(c, w["v_up"]["w"]).reshape(S, H, dv)
        scores = (jnp.einsum("qhd,thd->hqt", q[..., :dn], k_nope,
                             precision="highest")
                  + jnp.einsum("qhd,td->hqt", q_rope, k_rope,
                               precision="highest")) / math.sqrt(dn + dr)
        o = causal_softmax_mix(scores, v).reshape(S, H * dv)
    else:
        KVH, hd = conf["num_key_value_heads"], conf["head_dim"]
        q = rope(mm_(h, w["q"]["w"]).reshape(S, H, hd), pos, base)
        k = rope(mm_(h, w["k"]["w"]).reshape(S, KVH, hd), pos, base)
        v = mm_(h, w["v"]["w"]).reshape(S, KVH, hd)
        g = H // KVH                        # query head j reads kv head j // g
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,thd->hqt", q, k,
                            precision="highest") / math.sqrt(hd)
        o = causal_softmax_mix(scores, v).reshape(S, H * hd)
    return mm_(o, w["o"]["w"])


def _swiglu(p, x, mm_):
    return mm_(jax.nn.silu(mm_(x, p["gate"]["w"])) * mm_(x, p["up"]["w"]),
               p["down"]["w"])


def _moe(conf, p, x, mm_):
    """x [T, D] -> [T, D]: every expert on every token, gate-weighted."""
    k = conf["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(x, p["router"]["w"]), axis=-1)    # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    E = probs.shape[-1]
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)       # [T, E]

    def expert(y, e):
        h = jax.nn.silu(mm_(x, p["wg"][e])) * mm_(x, p["wi"][e])
        return y + gate[:, e, None] * mm_(h, p["wo"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    if "shared" in p:
        y = y + _swiglu(p["shared"], x, mm_)
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(conf_items, kind, precision, w, x):
    """x [N, S, D] float32 through one layer: attention sequence by
    sequence, the feed-forward over every position at once."""
    conf = dict(conf_items)
    mm_ = MATMUL[precision]
    eps = conf["rms_norm_eps"]
    N, S, D = x.shape
    x = x + jax.lax.map(
        lambda xs: _attention(conf, w["attn"],
                              rms(xs, w["ln1"]["scale"], eps), mm_), x)
    h = rms(x, w["ln2"]["scale"], eps).reshape(N * S, D)
    y = (_swiglu(w["mlp"], h, mm_) if kind == "prefix"
         else _moe(conf, w["moe"], h, mm_))
    return x + y.reshape(N, S, D)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(conf_items, key, tokens):
    return top(dict(conf_items), key)["embed"][tokens].astype(f32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_gaps(conf_items, precision, key, x, targets):
    """gap [N, S] = best logit - target's logit where target >= 0, and the
    best token at every position."""
    conf = dict(conf_items)
    mm_ = MATMUL[precision]
    t = top(conf, key)

    def one(args):
        xs, tg = args
        h = rms(xs, t["final_norm"]["scale"], conf["rms_norm_eps"])
        logits = mm_(h, t["lm_head"]["w"])                      # [S, V]
        best = jnp.max(logits, -1)
        mine = jnp.take_along_axis(logits, jnp.maximum(tg, 0)[:, None],
                                   -1)[:, 0]
        return (jnp.where(tg >= 0, best - mine, 0.0),
                jnp.argmax(logits, -1).astype(jnp.int32))

    return jax.lax.map(one, (x, targets))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _weights(conf_items, kind, key, i):
    return layer(dict(conf_items), key, kind, i)


def _items(conf):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "vocab_size", "rms_norm_eps", "rope_theta", "dtype",
            "first_k_dense_replace", "num_hidden_layers")
    return tuple(sorted((k, conf[k]) for k in keep if k in conf))


def hidden(conf, key, tokens, precision="f32"):
    """tokens [N, S] int32 -> the last layer's output [N, S, D], float32.
    ``precision`` "fp8" or "int8" computes every projection in that type
    (a control)."""
    items = _items(conf)
    with jax.default_matmul_precision("highest"):
        x = _embed(items, key, tokens)
        nk = n_prefix(conf)
        for i in range(conf["num_hidden_layers"]):
            kind, j = ("prefix", i) if i < nk else ("block", i - nk)
            w = _weights(items, kind, key, jnp.int32(j))
            x = _layer(items, kind, precision, w, x)
            del w
        return x


def head(conf, key, x, targets, precision="f32"):
    """x from ``hidden``; targets [N, S] int32 (the token the model was to
    put at position p + 1, or -1).  Returns (gap, best) [N, S]: how far
    each target lies below the best logit, and the best token."""
    with jax.default_matmul_precision("highest"):
        return _head_gaps(_items(conf), precision, key, x, targets)


# Work.  Every projection at 2 FLOPs per multiply-add, attention scores and
# values over the positions a token attends, the routed experts a token is
# sent to (not the capacity the program pads), shared experts, the router.
# Latent attention is counted in its expanded form.

def _attention_flops(conf, ctx: float) -> float:
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


def _ffn_flops(conf, dense: bool) -> float:
    D = conf["hidden_size"]
    if dense:
        return 6.0 * D * conf["intermediate_size"]
    F = conf["moe_intermediate_size"]
    E = _experts(conf)
    k = conf["num_experts_per_tok"]
    shared = conf.get("n_shared_experts", 0)
    return 2.0 * D * E + 6.0 * D * F * (k + shared)


def token(conf, ctx: float, logits: bool) -> float:
    """FLOPs of one token attending ``ctx`` positions (itself included),
    with the LM head where ``logits``."""
    nk = n_prefix(conf)
    L = conf["num_hidden_layers"]
    f = L * _attention_flops(conf, ctx) + nk * _ffn_flops(conf, True) \
        + (L - nk) * _ffn_flops(conf, False)
    if logits:
        f += 2.0 * conf["hidden_size"] * conf["vocab_size"]
    return f
