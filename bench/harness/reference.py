"""The plain reference: the configuration's forward pass in float32.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, with no
kernel, cache, batching by slot or capacity: full causal attention over
each whole sequence, and every routed expert computed for every token and
weighted by its renormalised top-k gate (zero where it is not routed).
It imports nothing of the program and reads the benchmark's own weights
(``weights.py``), one layer at a time, so it fits beside nothing else.

The architecture is the one the configuration file states as run:
RMSNorm with ``rms_norm_eps``; rotary embedding with base ``rope_theta``
on interleaved pairs (the GPT-J layout: the same function as the
rotate-half layout up to a fixed permutation of the q/k projection
columns); grouped-query attention, or multi-head latent attention with a
decoupled rotary key; SwiGLU experts, softmax router, top-k weights
renormalised (``norm_topk_prob: true``); shared experts added to every
token; the first ``first_k_dense_replace`` layers dense.

``gaps(conf, seed_key, tokens, targets)`` returns, at each position that
has a target, how far the target token's logit lies below the best logit.
With ``precision="fp8"`` or ``"int8"`` every projection is computed in
that type (activations with one scale per row, weights one per output
column, accumulated in float32; the router stays float32, as the program
keeps it): the two steps below the bfloat16 the configurations serve, the
controls of the check.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.weights import layer, n_prefix, top

f32 = jnp.float32


def _mm(x, w):
    return jnp.matmul(x, w.astype(f32), precision="highest")


def _fp8(v, axis):
    """Round to float8 e4m3, one scale per slice along ``axis`` that maps
    the slice's largest magnitude to the format's largest (448)."""
    s = jnp.max(jnp.abs(v), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (v / s).astype(jnp.float8_e4m3fn).astype(f32) * s


def _int8(v, axis):
    """Round to int8, one symmetric scale per slice along ``axis`` that
    maps the slice's largest magnitude to 127."""
    s = jnp.max(jnp.abs(v), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(v / s), -127, 127) * s


def _mm_below(rnd):
    """A control's matmul: activations rounded with one scale per row,
    weights with one scale per output column, accumulated in float32."""
    def mm(x, w):
        return jnp.matmul(rnd(x, -1), rnd(w.astype(f32), -2),
                          precision="highest")
    return mm


MATMUL = {"f32": _mm, "fp8": _mm_below(_fp8), "int8": _mm_below(_int8)}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(f32)


def _rope(x, pos, base):
    """x [S, heads, d]: rotate interleaved pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=f32) / d))
    ang = pos[:, None].astype(f32) * inv                      # [S, d/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def _causal_softmax_mix(scores, v):
    """scores [H, S, S] (query, key), v [S, H, dv] -> [S, H, dv]."""
    S = scores.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thd->qhd", p, v, precision="highest")


def _attention(conf, w, h, mm):
    """One sequence: h [S, D] (normed) -> [S, D]."""
    S = h.shape[0]
    H = conf["num_attention_heads"]
    pos = jnp.arange(S)
    base = float(conf["rope_theta"])
    if "kv_lora_rank" in conf:
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
        q = mm(h, w["q"]["w"]).reshape(S, H, dn + dr)
        ckr = mm(h, w["kv_down"]["w"])
        c = _rms(ckr[:, :r], w["kv_norm"]["scale"], conf["rms_norm_eps"])
        k_rope = _rope(ckr[:, None, r:], pos, base)[:, 0]     # [S, dr]
        q_rope = _rope(q[..., dn:], pos, base)
        k_nope = mm(c, w["k_up"]["w"]).reshape(S, H, dn)
        v = mm(c, w["v_up"]["w"]).reshape(S, H, dv)
        scores = (jnp.einsum("qhd,thd->hqt", q[..., :dn], k_nope,
                             precision="highest")
                  + jnp.einsum("qhd,td->hqt", q_rope, k_rope,
                               precision="highest")) / math.sqrt(dn + dr)
        o = _causal_softmax_mix(scores, v).reshape(S, H * dv)
    else:
        KVH, hd = conf["num_key_value_heads"], conf["head_dim"]
        q = _rope(mm(h, w["q"]["w"]).reshape(S, H, hd), pos, base)
        k = _rope(mm(h, w["k"]["w"]).reshape(S, KVH, hd), pos, base)
        v = mm(h, w["v"]["w"]).reshape(S, KVH, hd)
        g = H // KVH                        # query head j reads kv head j // g
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("qhd,thd->hqt", q, k,
                            precision="highest") / math.sqrt(hd)
        o = _causal_softmax_mix(scores, v).reshape(S, H * hd)
    return mm(o, w["o"]["w"])


def _swiglu(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["gate"]["w"])) * mm(x, p["up"]["w"]),
              p["down"]["w"])


def _moe(conf, p, x, mm):
    """x [T, D] -> [T, D]: every expert on every token, gate-weighted."""
    k = conf["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(x, p["router"]["w"]), axis=-1)   # [T, E]
    top_w, top_i = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    E = probs.shape[-1]
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)       # [T, E]

    def expert(y, e):
        h = jax.nn.silu(mm(x, p["wg"][e])) * mm(x, p["wi"][e])
        return y + gate[:, e, None] * mm(h, p["wo"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    if "shared" in p:
        y = y + _swiglu(p["shared"], x, mm)
    return y


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(conf_items, kind, precision, w, x):
    """x [N, S, D] float32 through one layer: attention sequence by
    sequence, the feed-forward over every position at once."""
    conf = dict(conf_items)
    mm = MATMUL[precision]
    eps = conf["rms_norm_eps"]
    N, S, D = x.shape
    x = x + jax.lax.map(
        lambda xs: _attention(conf, w["attn"],
                              _rms(xs, w["ln1"]["scale"], eps), mm), x)
    h = _rms(x, w["ln2"]["scale"], eps).reshape(N * S, D)
    y = (_swiglu(w["mlp"], h, mm) if kind == "prefix"
         else _moe(conf, w["moe"], h, mm))
    return x + y.reshape(N, S, D)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(conf_items, key, tokens):
    return top(dict(conf_items), key)["embed"][tokens].astype(f32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_gaps(conf_items, precision, key, x, targets):
    """gap [N, S] = best logit - target's logit where target >= 0, and the
    best token at every position."""
    conf = dict(conf_items)
    mm = MATMUL[precision]
    t = top(conf, key)

    def one(args):
        xs, tg = args
        h = _rms(xs, t["final_norm"]["scale"], conf["rms_norm_eps"])
        logits = mm(h, t["lm_head"]["w"])                       # [S, V]
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


def gaps(conf, key, tokens, targets, precision="f32"):
    """``head`` over ``hidden``: (gap, best) [N, S]."""
    return head(conf, key, hidden(conf, key, tokens, precision), targets,
                precision)
