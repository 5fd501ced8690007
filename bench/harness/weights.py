"""Random weights from the run's seed, made by the benchmark.

The program serves them and the reference reads them; neither takes
weights the other made.  ``params(conf, key)`` builds the whole tree in the
program's parameter layout in one jitted call on the device, in the type
the configuration serves; ``layer(conf, key, kind, i)`` rebuilds one layer
alone with the same draws, so the reference never holds more than a layer.

Every leaf is drawn from its own key, ``fold_in(fold_in(key, part), leaf)``,
so a layer's values do not depend on which other layers are built with it.
Scales: ``N(0, 1/fan_in)`` for projections, ``N(0, 0.02^2)`` for the
embedding, ones for norm scales; the router is float32, as the program
keeps it.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# part ids: one key family per stacked layer kind and for the top level
_TOP, _PREFIX, _BLOCK = 0, 1, 2
_LEAVES = ("embed", "lm_head", "q", "k", "v", "o", "kv_down", "k_up", "v_up",
           "router", "wi", "wg", "wo", "up", "gate", "down", "sh_up",
           "sh_gate", "sh_down")


def seed_key(seed: int) -> jax.Array:
    """The legacy uint32[2] key for any whole-number seed.  Seeds under
    2**32 map to ``PRNGKey(seed)``; higher bits are folded in."""
    return jax.random.PRNGKey(fold32(seed))


def fold32(seed: int) -> int:
    seed = int(seed)
    out = 0
    while True:
        out ^= seed & 0xFFFFFFFF
        seed >>= 32
        if not seed:
            return out


def _normal(key, name, shape, scale, dtype):
    k = jax.random.fold_in(key, _LEAVES.index(name))
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def _ones(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def _lin(key, name, d_in, d_out, dtype):
    return {"w": _normal(key, name, (d_in, d_out), 1 / math.sqrt(d_in), dtype)}


def _attention(conf, key, dtype) -> Dict[str, Any]:
    D, H = conf["hidden_size"], conf["num_attention_heads"]
    if "kv_lora_rank" in conf:       # latent attention (MLA), full-rank q
        dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
        dv, r = conf["v_head_dim"], conf["kv_lora_rank"]
        return {"q": _lin(key, "q", D, H * (dn + dr), dtype),
                "kv_down": _lin(key, "kv_down", D, r + dr, dtype),
                "kv_norm": _ones(r, dtype),
                "k_up": _lin(key, "k_up", r, H * dn, dtype),
                "v_up": _lin(key, "v_up", r, H * dv, dtype),
                "o": _lin(key, "o", H * dv, D, dtype)}
    KVH, hd = conf["num_key_value_heads"], conf["head_dim"]
    return {"q": _lin(key, "q", D, H * hd, dtype),
            "k": _lin(key, "k", D, KVH * hd, dtype),
            "v": _lin(key, "v", D, KVH * hd, dtype),
            "o": _lin(key, "o", H * hd, D, dtype)}


def _mlp(key, names, D, F, dtype):
    up, gate, down = names
    return {"up": _lin(key, up, D, F, dtype),
            "down": _lin(key, down, F, D, dtype),
            "gate": _lin(key, gate, D, F, dtype)}


def _experts(conf):
    return conf.get("num_experts", conf.get("n_routed_experts", 0))


def _moe(conf, key, dtype) -> Dict[str, Any]:
    D, F, E = conf["hidden_size"], conf["moe_intermediate_size"], \
        _experts(conf)
    p = {"router": _lin(key, "router", D, E, jnp.float32),
         "wi": _normal(key, "wi", (E, D, F), 1 / math.sqrt(D), dtype),
         "wg": _normal(key, "wg", (E, D, F), 1 / math.sqrt(D), dtype),
         "wo": _normal(key, "wo", (E, F, D), 1 / math.sqrt(F), dtype)}
    shared = conf.get("n_shared_experts", 0)
    if shared:
        p["shared"] = _mlp(key, ("sh_up", "sh_gate", "sh_down"), D,
                           F * shared, dtype)
    return p


def layer(conf, key, kind: str, i: int) -> Dict[str, Any]:
    """One layer's leaves: ``kind`` is ``"prefix"`` (a leading dense layer)
    or ``"block"`` (a MoE layer), ``i`` its index among its kind."""
    dtype = jnp.dtype(conf["dtype"])
    D = conf["hidden_size"]
    part = _PREFIX if kind == "prefix" else _BLOCK
    k = jax.random.fold_in(jax.random.fold_in(key, part), i)
    p = {"ln1": _ones(D, dtype), "ln2": _ones(D, dtype),
         "attn": _attention(conf, k, dtype)}
    if kind == "prefix":
        p["mlp"] = _mlp(k, ("up", "gate", "down"), D,
                        conf["intermediate_size"], dtype)
    else:
        p["moe"] = _moe(conf, k, dtype)
    return p


def top(conf, key) -> Dict[str, Any]:
    dtype = jnp.dtype(conf["dtype"])
    D, V = conf["hidden_size"], conf["vocab_size"]
    k = jax.random.fold_in(key, _TOP)
    return {"final_norm": _ones(D, dtype),
            "embed": _normal(k, "embed", (V, D), 0.02, dtype),
            "lm_head": _lin(k, "lm_head", D, V, dtype)}


def n_prefix(conf) -> int:
    return conf.get("first_k_dense_replace", 0)


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


def program_init(conf):
    """A stand-in for the program's ``init_params(cfg, rng, dtype)``: the
    program's boot calls it in one jitted call with its seed's key, which
    the harness sets to ``seed_key(seed)``."""
    def init(cfg, rng, dtype=None):
        return params(conf, rng)
    return init


def check_layout(conf, program_init_params, mcfg) -> None:
    """Raise unless the benchmark's tree has the program's structure,
    shapes and types exactly."""
    ours = jax.eval_shape(lambda: params(conf, jax.random.PRNGKey(0)))
    theirs = jax.eval_shape(lambda: program_init_params(
        mcfg, jax.random.PRNGKey(0), jnp.dtype(mcfg.dtype)))
    a = jax.tree_util.tree_flatten_with_path(ours)
    b = jax.tree_util.tree_flatten_with_path(theirs)
    if a[1] != b[1]:
        raise SystemExit(f"weight tree differs from the program's: "
                         f"{a[1]} vs {b[1]}")
    for (path, x), (_, y) in zip(a[0], b[0]):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise SystemExit(f"weight {jax.tree_util.keystr(path)}: "
                             f"{x.shape} {x.dtype} vs the program's "
                             f"{y.shape} {y.dtype}")

