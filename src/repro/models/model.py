"""Composable model definition: build train/prefill/decode functions from a
:class:`ModelConfig`.

Layer stacks are ``lax.scan``-rolled (stacked parameter pytrees) so the HLO
stays small at any depth — essential for the 80-way multi-pod dry-run compile
matrix.  Architecturally non-uniform layers are factored into separate stacks:

* moe archs with ``first_k_dense``: a small unstacked prefix + a scanned MoE
  stack,
* vlm: superblocks of ``cross_attn_every`` self-attn layers with one
  cross-attn block at the head of each superblock,
* hybrid (zamba2): groups of ``attn_every`` SSM blocks with one *shared*
  attention block (weights shared across all applications) at the head of
  each group.

Public entry points
-------------------
``init_params``, ``forward`` (training), ``loss_fn``, ``prefill``,
``decode_step``, ``init_cache``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mamba2 as m2
from repro.models import mla as mla_mod
from repro.models.layers import (apply_norm, attention_init, attention_apply,
                                 linear, linear_init, mlp_apply, mlp_init,
                                 norm_init)
from repro.models.moe import moe_ep, moe_init, moe_local, moe_local_pooled

Params = Dict[str, Any]


# ------------------------------------------------------------------ builders

def _block_init(rng, cfg: ModelConfig, dtype, *, moe: bool, cross: bool = False):
    ks = jax.random.split(rng, 6)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm_type, dtype),
         "ln2": norm_init(cfg.d_model, cfg.norm_type, dtype)}
    if cfg.use_mla:
        p["attn"] = mla_mod.mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = attention_init(ks[0], cfg, dtype)
    if cross:
        p["xattn"] = attention_init(ks[1], cfg, dtype)
        p["lnx"] = norm_init(cfg.d_model, cfg.norm_type, dtype)
        p["xgate"] = jnp.zeros((1,), dtype)
    if moe:
        p["moe"] = moe_init(ks[2], cfg, dtype)
        if cfg.dense_residual:
            p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, dtype,
                                cfg.mlp_gated)
    else:
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, dtype, cfg.mlp_gated)
    return p


def _ssm_block_init(rng, cfg, dtype):
    return {"ln": norm_init(cfg.d_model, cfg.norm_type, dtype),
            "ssm": m2.mamba2_init(rng, cfg, dtype)}


def _stack_init(rng, n, one_init):
    return jax.vmap(one_init)(jax.random.split(rng, n))


def init_params(cfg: ModelConfig, rng, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 8)
    p: Params = {"final_norm": norm_init(cfg.d_model, cfg.norm_type, dtype)}
    if cfg.arch_type != "encoder":
        p["embed"] = (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                        dtype) * 0.02)
    p["lm_head"] = linear_init(ks[1], cfg.d_model, cfg.vocab_size, dtype)

    if cfg.arch_type == "ssm":
        p["blocks"] = _stack_init(
            ks[2], cfg.num_layers, lambda k: _ssm_block_init(k, cfg, dtype))
    elif cfg.arch_type == "hybrid":
        ng = cfg.num_layers // cfg.attn_every
        p["blocks"] = _stack_init(
            ks[2], cfg.num_layers, lambda k: _ssm_block_init(k, cfg, dtype))
        p["shared_attn"] = _block_init(ks[3], cfg, dtype, moe=False)
    elif cfg.arch_type == "vlm":
        # num_layers total = ncross cross-attn layers (one leading each
        # superblock) + the remaining self-attn layers.
        ncross = cfg.num_layers // cfg.cross_attn_every
        p["blocks"] = _stack_init(
            ks[2], cfg.num_layers - ncross,
            lambda k: _block_init(k, cfg, dtype, moe=False))
        p["cross_blocks"] = _stack_init(
            ks[3], ncross,
            lambda k: _block_init(k, cfg, dtype, moe=False, cross=True))
    elif cfg.is_moe:
        nk = cfg.first_k_dense
        if nk:
            p["dense_prefix"] = [
                _block_init(k, cfg, dtype, moe=False)
                for k in jax.random.split(ks[2], nk)]
        p["blocks"] = _stack_init(
            ks[3], cfg.num_layers - nk,
            lambda k: _block_init(k, cfg, dtype, moe=True))
    else:  # dense / encoder
        p["blocks"] = _stack_init(
            ks[2], cfg.num_layers,
            lambda k: _block_init(k, cfg, dtype, moe=False))
    return p


# -------------------------------------------------------------- block apply

def _ffn_part(cfg, bp, h, *, parallel, moe: bool, moe_capacity=None,
              moe_pool=None, return_counts=False):
    """Post-attention feed-forward (+MoE).  Returns (y, aux) — or
    (y, aux, counts [E] int32) with ``return_counts`` (routing telemetry,
    DESIGN.md §9; zeros for non-MoE layers).

    ``moe_pool``: the pooled expert weight store (``params["moe_pool"]``,
    shared across layers) when the HMM runs ``expert_mode="pooled"``; the
    per-layer ``bp["moe"]`` then carries page-table index arrays instead of
    dense [E, D, F] banks (models/moe.py).  The index arrays are the ONLY
    coupling to expert placement: the skew rebalancer (DESIGN.md §10) swaps
    them in place between ticks to re-point hot experts at byte-identical
    replicas, with no change to this forward pass or its compiled shape."""
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((cfg.num_experts,), jnp.int32)
    if moe:
        if parallel is not None:
            out = moe_ep(cfg, bp["moe"], h, parallel, capacity=moe_capacity,
                         pool=moe_pool, return_counts=return_counts)
            y, aux = out[0], out[1]
            if return_counts:
                counts = out[2]
        else:
            B, S, D = h.shape
            if moe_pool is not None and "tables" in bp["moe"]:
                out = moe_local_pooled(cfg, bp["moe"], moe_pool,
                                       h.reshape(B * S, D),
                                       capacity=moe_capacity,
                                       return_counts=return_counts)
            else:
                out = moe_local(cfg, bp["moe"], h.reshape(B * S, D),
                                capacity=moe_capacity,
                                return_counts=return_counts)
            yf, aux = out[0], out[1]
            if return_counts:
                counts = out[2]
            y = yf.reshape(B, S, D)
        if cfg.dense_residual:
            y = y + mlp_apply(bp["mlp"], h, cfg.mlp_gated)
    else:
        y = mlp_apply(bp["mlp"], h, cfg.mlp_gated)
    if return_counts:
        return y, aux, counts
    return y, aux


def _attn_block(cfg, bp, x, positions, *, cache=None, write_pos=None,
                kv_valid_len=None, image_kv=None, image_x=None,
                parallel=None, moe=False, moe_capacity=None, moe_pool=None,
                collect_routing=False):
    """Generic (self-attn [+cross-attn] + ffn/moe) block.

    Returns (x', new_kv_cache, new_image_kv, aux) — plus a trailing
    per-expert routing-count vector [E] when ``collect_routing``.
    """
    h = apply_norm(bp["ln1"], x, cfg.norm_type)
    if cfg.use_mla:
        if cache is None:
            a, new_kv = mla_mod.mla_prefill(cfg, bp["attn"], h, positions)
        else:
            a, new_kv = mla_mod.mla_decode(cfg, bp["attn"], h, positions,
                                           cache, write_pos, kv_valid_len)
    else:
        a, new_kv = attention_apply(cfg, bp["attn"], h, positions,
                                    cache=cache, write_pos=write_pos,
                                    kv_valid_len=kv_valid_len)
    x = x + a
    new_image_kv = image_kv
    if "xattn" in bp:
        hx = apply_norm(bp["lnx"], x, cfg.norm_type)
        if image_kv is not None:          # decode: attend over cached image kv
            cx, _ = attention_apply(cfg, bp["xattn"], hx, positions,
                                    cache=image_kv, causal=False, rope=False)
        else:                             # prefill: compute image kv
            cx, new_image_kv = attention_apply(
                cfg, bp["xattn"], hx, positions, kv_x=image_x,
                causal=False, rope=False)
        x = x + jnp.tanh(bp["xgate"]) * cx
    h = apply_norm(bp["ln2"], x, cfg.norm_type)
    out = _ffn_part(cfg, bp, h, parallel=parallel, moe=moe,
                    moe_capacity=moe_capacity, moe_pool=moe_pool,
                    return_counts=collect_routing)
    if collect_routing:
        y, aux, counts = out
        return x + y, new_kv, new_image_kv, aux, counts
    y, aux = out
    return x + y, new_kv, new_image_kv, aux


def _ssm_block(cfg, bp, x, *, cache=None):
    h = apply_norm(bp["ln"], x, cfg.norm_type)
    if cache is None:
        y, new_cache = m2.mamba2_forward(cfg, bp["ssm"], h, return_cache=True)
    else:
        y, new_cache = m2.mamba2_decode(cfg, bp["ssm"], h, cache)
    return x + y, new_cache


# ---------------------------------------------------------------- forward

def _embed(cfg, params, batch):
    if cfg.arch_type == "encoder":
        return batch["frames"]
    return jnp.take(params["embed"], batch["tokens"], axis=0)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, parallel=None, remat: bool = True):
    """Full-sequence forward (training / evaluation).

    batch: tokens [B,S] (or frames [B,S,D] for encoder archs),
           image_embeds [B,T_img,D] for vlm.
    Returns (logits [B,S,V], aux_loss scalar).
    """
    x = _embed(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    aux_total = jnp.zeros((), jnp.float32)

    def maybe_remat(f):
        return jax.checkpoint(f) if remat else f

    if cfg.arch_type == "ssm":
        def body(x, bp):
            x, _ = _ssm_block(cfg, bp, x)
            return x, None
        x, _ = jax.lax.scan(maybe_remat(body), x, params["blocks"])

    elif cfg.arch_type == "hybrid":
        ng = cfg.num_layers // cfg.attn_every
        blocks = jax.tree.map(
            lambda t: t.reshape(ng, cfg.attn_every, *t.shape[1:]),
            params["blocks"])
        shared = params["shared_attn"]

        def group(x, bps):
            x, _, _, _ = _attn_block(cfg, shared, x, positions)
            def inner(x, bp):
                x, _ = _ssm_block(cfg, bp, x)
                return x, None
            x, _ = jax.lax.scan(inner, x, bps)
            return x, None
        x, _ = jax.lax.scan(maybe_remat(group), x, blocks)

    elif cfg.arch_type == "vlm":
        every = cfg.cross_attn_every
        ng = cfg.num_layers // every
        blocks = jax.tree.map(
            lambda t: t.reshape(ng, every - 1, *t.shape[1:]), params["blocks"])
        img = batch["image_embeds"]

        def group(x, bps):
            bcross, bselfs = bps
            x, _, _, _ = _attn_block(cfg, bcross, x, positions, image_x=img)
            def inner(x, bp):
                x, _, _, _ = _attn_block(cfg, bp, x, positions)
                return x, None
            x, _ = jax.lax.scan(inner, x, bselfs)
            return x, None
        x, _ = jax.lax.scan(maybe_remat(group), x,
                            (params["cross_blocks"], blocks))

    else:
        moe = cfg.is_moe
        if moe and cfg.first_k_dense:
            for bp in params["dense_prefix"]:
                x, _, _, _ = _attn_block(cfg, bp, x, positions)

        def body(carry, bp):
            x, aux = carry
            x, _, _, a = _attn_block(cfg, bp, x, positions, parallel=parallel,
                                     moe=moe, moe_pool=params.get("moe_pool"))
            return (x, aux + a), None
        (x, aux_total), _ = jax.lax.scan(maybe_remat(body),
                                         (x, aux_total), params["blocks"])

    x = apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = linear(params["lm_head"], x)
    return logits, aux_total


def loss_fn(cfg, params, batch, *, parallel=None, remat=True):
    logits, aux = forward(cfg, params, batch, parallel=parallel, remat=remat)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        loss = jnp.mean(nll)
    else:
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + cfg.router_aux_coef * aux


# ------------------------------------------------------------------- caches

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """Decode cache pytree sized for ``max_len`` tokens."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    if cfg.arch_type == "ssm" or cfg.arch_type == "hybrid":
        di, N = cfg.d_inner, cfg.ssm_state
        H, P = cfg.ssm_heads, cfg.ssm_head_dim
        cache = {
            "conv": jnp.zeros((L, batch, cfg.ssm_conv - 1, di + 2 * N), dtype),
            "state": jnp.zeros((L, batch, H, N, P), jnp.float32),
        }
        if cfg.arch_type == "hybrid":
            ng = cfg.num_layers // cfg.attn_every
            KVH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            cache["attn_k"] = jnp.zeros((ng, batch, max_len, KVH, hd), dtype)
            cache["attn_v"] = jnp.zeros((ng, batch, max_len, KVH, hd), dtype)
        return cache
    if cfg.use_mla:
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
        return {"c": jnp.zeros((L, batch, max_len, r), dtype),
                "kr": jnp.zeros((L, batch, max_len, dr), dtype)}
    KVH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    eff = max_len if cfg.attn_window is None else min(max_len, cfg.attn_window)
    cache = {"k": jnp.zeros((L, batch, eff, KVH, hd), dtype),
             "v": jnp.zeros((L, batch, eff, KVH, hd), dtype)}
    if cfg.arch_type == "vlm":
        ncross = cfg.num_layers // cfg.cross_attn_every
        cache["img_k"] = jnp.zeros((ncross, batch, cfg.num_image_tokens,
                                    KVH, hd), dtype)
        cache["img_v"] = jnp.zeros_like(cache["img_k"])
    return cache


def routing_stats_supported(cfg: ModelConfig) -> bool:
    """Per-expert routing telemetry rides the decode step as an extra
    [L_moe, E] count output (``decode_step(..., collect_routing=True)``);
    covered family = standard-attention MoE decoders — the same scanned MoE
    decode paths the serving engine compiles; DESIGN.md §9."""
    return (cfg.has_decode and cfg.arch_type == "moe"
            and not cfg.use_mla and cfg.attn_window is None)


def paged_cache_supported(cfg: ModelConfig) -> bool:
    """The block-managed KV layout covers standard-attention decoders
    (dense + MoE).  MLA/SSM/hybrid/VLM state and windowed attention keep the
    dense layout (their caches are not per-token-appendable in the same
    way); DESIGN.md §7."""
    return (cfg.has_decode and cfg.arch_type in ("dense", "moe")
            and not cfg.use_mla and cfg.attn_window is None)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=None, kv_dtype=None):
    """Block-pool decode cache: {'k','v': [L, NB, bs, KVH, hd]}.

    One pool row per (layer, block); ``serving/kv_blocks.py`` owns which
    sequence maps to which rows.  The block axis is sharded over 'dp'
    (one partition of ``NB/dp`` rows per replica), so growing the instance
    appends partitions and surviving rows are reused zero-copy.  The
    paged steps carry the whole stacked pool through the layer scan and
    never slice it: layer ``l`` writes and reads pool layer ``l`` by index,
    inside the attention kernel (``kernels/paged_attention.py``).

    ``kv_dtype="int8"`` stores entries quantized (DESIGN.md §11): the pools
    become int8 and per-token f32 scale pools ``{'k_scale','v_scale':
    [L, NB, bs]}`` ride beside them.  Every leaf keeps the block axis at
    axis 1, so the engine's CoW copies, per-block byte accounting, growth
    adoption and live migration treat scales exactly like entries — a
    block's scales provably travel with it.
    """
    assert paged_cache_supported(cfg), \
        f"{cfg.name}: paged KV requires a standard-attention decoder"
    dtype = dtype or jnp.dtype(cfg.dtype)
    L = cfg.num_layers
    KVH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if kv_dtype is not None and jnp.dtype(kv_dtype) != jnp.dtype(dtype):
        assert jnp.dtype(kv_dtype) == jnp.int8, \
            f"unsupported kv_dtype {kv_dtype} (int8 or the model dtype)"
        return {"k": jnp.zeros((L, num_blocks, block_size, KVH, hd),
                               jnp.int8),
                "v": jnp.zeros((L, num_blocks, block_size, KVH, hd),
                               jnp.int8),
                "k_scale": jnp.zeros((L, num_blocks, block_size),
                                     jnp.float32),
                "v_scale": jnp.zeros((L, num_blocks, block_size),
                                     jnp.float32)}
    return {"k": jnp.zeros((L, num_blocks, block_size, KVH, hd), dtype),
            "v": jnp.zeros((L, num_blocks, block_size, KVH, hd), dtype)}


def write_prefill_to_blocks(cache, dense_cache, block_ids):
    """Scatter one sequence's dense prefill KV ([L, 1, S, KVH, hd]) into its
    pool blocks.  ``block_ids`` [S/bs] holds the pool row per prompt chunk;
    entries == NB are dropped — the engine passes the sentinel both for
    padding chunks beyond the prompt and for CoW-shared prefix blocks, which
    must NOT be rewritten (they hold another live sequence's identical
    prefix, plus possibly its tokens beyond this prompt's length).

    On a quantized pool the f32 prefill rows are quantized per token at
    write time and the scales scatter through the same block ids."""
    bs = cache["k"].shape[2]
    nb = block_ids.shape[0]

    def rows_of(small):
        L = small.shape[0]
        return small[:, 0, :nb * bs].reshape(L, nb, bs, *small.shape[3:])

    if "k_scale" in cache:
        from repro.kernels.quant import quantize_rows
        out = dict(cache)
        for name in ("k", "v"):
            q, s = quantize_rows(rows_of(dense_cache[name]), (-2, -1))
            out[name] = cache[name].at[:, block_ids].set(q, mode="drop")
            out[name + "_scale"] = cache[name + "_scale"].at[
                :, block_ids].set(s, mode="drop")
        return out

    def put(pool, small):
        return pool.at[:, block_ids].set(rows_of(small).astype(pool.dtype),
                                         mode="drop")

    return {"k": put(cache["k"], dense_cache["k"]),
            "v": put(cache["v"], dense_cache["v"])}


def paged_decode_step(cfg: ModelConfig, params: Params, tokens, cache,
                      lengths, block_tables, write_block, *, parallel=None,
                      collect_routing=False):
    """One decode step over the paged KV pool.  tokens [B,1]; lengths [B];
    block_tables [B,MB] (pool rows per sequence, position-ordered);
    write_block [B] = row receiving this token's k/v (== NB for inactive
    slots -> dropped).  Returns (logits [B,V], cache') — plus per-layer
    routing counts [L_moe, E] when ``collect_routing`` (dense-prefix layers
    have no router and contribute no row)."""
    from repro.models.layers import paged_attention_apply

    if collect_routing:
        assert routing_stats_supported(cfg), \
            f"{cfg.name}: routing telemetry unsupported"
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = lengths[:, None]
    moe = cfg.is_moe

    def block(bp, x, cache, layer, want_counts=False):
        h = apply_norm(bp["ln1"], x, cfg.norm_type)
        a, cache = paged_attention_apply(
            cfg, bp["attn"], h, positions, cache=cache, layer=layer,
            block_tables=block_tables, write_block=write_block,
            lengths=lengths, parallel=parallel)
        x = x + a
        h = apply_norm(bp["ln2"], x, cfg.norm_type)
        out = _ffn_part(cfg, bp, h, parallel=parallel,
                        moe=moe and "moe" in bp,
                        moe_pool=params.get("moe_pool"),
                        return_counts=want_counts)
        if want_counts:
            y, _, cnt = out
            return x + y, cache, cnt
        y, _ = out
        return x + y, cache

    # the stacked pool is the scan's carry, not a scanned input: each layer
    # writes and reads its own pool layer in place, by index
    nk = cfg.first_k_dense if moe else 0
    for i in range(nk):
        x, cache = block(params["dense_prefix"][i], x, cache, i)

    def body(carry, inp):
        x, cache = carry
        bp, layer = inp
        if collect_routing:
            x, cache, cnt = block(bp, x, cache, layer, want_counts=True)
            return (x, cache), cnt
        x, cache = block(bp, x, cache, layer)
        return (x, cache), None

    (x, new_cache), counts = jax.lax.scan(
        body, (x, cache),
        (params["blocks"], jnp.arange(nk, cfg.num_layers, dtype=jnp.int32)))

    x = apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = linear(params["lm_head"], x[:, 0])
    if collect_routing:
        return logits, new_cache, counts
    return logits, new_cache


def chunk_prefill_supported(cfg: ModelConfig) -> bool:
    """Chunked prefill needs per-chunk-appendable KV with explicit position
    masking — the same family the paged layout covers (dense + MoE standard
    attention), for both KV layouts; DESIGN.md §8."""
    return (cfg.has_decode and cfg.arch_type in ("dense", "moe")
            and not cfg.use_mla and cfg.attn_window is None)


def paged_chunk_prefill_step(cfg: ModelConfig, params: Params, tokens, cache,
                             start, length, block_tables, chunk_block_ids,
                             *, parallel=None):
    """One chunked-prefill step for a single sequence over the paged pool.

    tokens [1,C] — one prompt chunk at absolute positions start..start+C-1
    (rows at or beyond the prompt length are padding); ``start`` scalar =
    chunk offset (block-aligned); ``length`` scalar = context tokens after
    this chunk (= min(start+C, prompt_len)); block_tables [1,MB] = the
    sequence's full table; chunk_block_ids [C/bs] = pool rows receiving this
    chunk's k/v (NB for padding/CoW-shared rows -> dropped).  Returns
    (logits [1,V] at position ``length-1``, cache') — the final chunk's
    logits sample the first output token, exactly like monolithic prefill.
    """
    from repro.models.layers import paged_chunk_attention_apply

    C = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = start + jnp.broadcast_to(jnp.arange(C)[None], (1, C))
    q_len = length - start
    moe = cfg.is_moe

    def block(bp, x, cache, layer):
        h = apply_norm(bp["ln1"], x, cfg.norm_type)
        a, cache = paged_chunk_attention_apply(
            cfg, bp["attn"], h, positions, cache=cache, layer=layer,
            block_tables=block_tables, chunk_block_ids=chunk_block_ids,
            ctx_len=length, q_len=q_len, parallel=parallel)
        x = x + a
        h = apply_norm(bp["ln2"], x, cfg.norm_type)
        y, _ = _ffn_part(cfg, bp, h, parallel=parallel,
                         moe=moe and "moe" in bp,
                         moe_pool=params.get("moe_pool"))
        return x + y, cache

    # the stacked pool is the scan's carry, as in paged_decode_step
    nk = cfg.first_k_dense if moe else 0
    for i in range(nk):
        x, cache = block(params["dense_prefix"][i], x, cache, i)

    def body(carry, inp):
        x, cache = carry
        bp, layer = inp
        return block(bp, x, cache, layer), None

    (x, new_cache), _ = jax.lax.scan(
        body, (x, cache),
        (params["blocks"], jnp.arange(nk, cfg.num_layers, dtype=jnp.int32)))

    x = apply_norm(params["final_norm"], x, cfg.norm_type)
    last = jax.lax.dynamic_index_in_dim(x, q_len - 1, axis=1, keepdims=False)
    logits = linear(params["lm_head"], last)
    return logits, new_cache


def chunk_prefill_step(cfg: ModelConfig, params: Params, tokens, cache,
                       start, length, slot, *, parallel=None):
    """Dense-layout twin of :func:`paged_chunk_prefill_step`: the chunk's
    k/v land in slot row ``slot`` of the slot-contiguous cache
    {'k','v': [L,B,S_max,KVH,hd]} at [start, start+C), and the chunk attends
    causally over the row.  Returns (logits [1,V] at ``length-1``, cache').
    """
    from repro.models.layers import chunk_attention_apply

    C = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = start + jnp.broadcast_to(jnp.arange(C)[None], (1, C))
    q_len = length - start
    moe = cfg.is_moe

    def block(bp, x, kfull, vfull):
        k_row = jax.lax.dynamic_slice_in_dim(kfull, slot, 1, axis=0)
        v_row = jax.lax.dynamic_slice_in_dim(vfull, slot, 1, axis=0)
        h = apply_norm(bp["ln1"], x, cfg.norm_type)
        a, (k_row, v_row) = chunk_attention_apply(
            cfg, bp["attn"], h, positions, k_row=k_row, v_row=v_row,
            start=start)
        kfull = jax.lax.dynamic_update_slice_in_dim(kfull, k_row, slot, axis=0)
        vfull = jax.lax.dynamic_update_slice_in_dim(vfull, v_row, slot, axis=0)
        x = x + a
        h = apply_norm(bp["ln2"], x, cfg.norm_type)
        y, _ = _ffn_part(cfg, bp, h, parallel=parallel,
                         moe=moe and "moe" in bp,
                         moe_pool=params.get("moe_pool"))
        return x + y, kfull, vfull

    nk = cfg.first_k_dense if moe else 0
    new_k, new_v = [], []
    for i in range(nk):
        x, kf, vf = block(params["dense_prefix"][i], x,
                          cache["k"][i], cache["v"][i])
        new_k.append(kf)
        new_v.append(vf)

    def body(x, inp):
        bp, kf, vf = inp
        x, kf, vf = block(bp, x, kf, vf)
        return x, (kf, vf)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"],
                                         cache["k"][nk:], cache["v"][nk:]))
    if nk:
        ks = jnp.concatenate([jnp.stack(new_k), ks], 0)
        vs = jnp.concatenate([jnp.stack(new_v), vs], 0)
    new_cache = {"k": ks, "v": vs}

    x = apply_norm(params["final_norm"], x, cfg.norm_type)
    last = jax.lax.dynamic_index_in_dim(x, q_len - 1, axis=1, keepdims=False)
    logits = linear(params["lm_head"], last)
    return logits, new_cache


def _cache_slot(cfg, lengths):
    """KV write slot for each sequence (ring-buffered under attn_window)."""
    if cfg.attn_window is None:
        return lengths
    return lengths % cfg.attn_window


# ---------------------------------------------------------------- prefill

def prefill(cfg: ModelConfig, params: Params, batch, max_len: int,
            *, parallel=None):
    """Process the full prompt; returns (last-token logits [B,V], cache).

    All sequences are assumed left-aligned; ``batch['lengths']`` [B] gives the
    true prompt lengths (padding tokens attend causally but their kv entries
    beyond length are masked at decode time via valid-length masking).
    """
    assert cfg.has_decode, f"{cfg.name} is encoder-only (no decode)"
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    cache = init_cache(cfg, B, max_len, jnp.dtype(cfg.dtype))

    def pad_to(t, length, axis):
        pads = [(0, 0)] * t.ndim
        pads[axis] = (0, length - t.shape[axis])
        return jnp.pad(t, pads)

    if cfg.arch_type in ("ssm", "hybrid"):
        if cfg.arch_type == "ssm":
            def body(x, bp):
                x, c = _ssm_block(cfg, bp, x)
                return x, c
            x, caches = jax.lax.scan(body, x, params["blocks"])
            cache = {"conv": caches["conv"], "state": caches["state"]}
        else:
            ng = cfg.num_layers // cfg.attn_every
            blocks = jax.tree.map(
                lambda t: t.reshape(ng, cfg.attn_every, *t.shape[1:]),
                params["blocks"])
            shared = params["shared_attn"]
            eff = cache["attn_k"].shape[2]

            def group(x, bps):
                x, kv, _, _ = _attn_block(cfg, shared, x, positions)
                k, v = kv
                def inner(x, bp):
                    x, c = _ssm_block(cfg, bp, x)
                    return x, c
                x, cs = jax.lax.scan(inner, x, bps)
                return x, (cs, pad_to(k[:, -eff:], eff, 1),
                           pad_to(v[:, -eff:], eff, 1))
            x, (cs, ks, vs) = jax.lax.scan(group, x, blocks)
            cache = {
                "conv": jax.tree.map(lambda t: t.reshape(cfg.num_layers,
                                                         *t.shape[2:]),
                                     cs["conv"]),
                "state": cs["state"].reshape(cfg.num_layers,
                                             *cs["state"].shape[2:]),
                "attn_k": ks, "attn_v": vs,
            }
    elif cfg.use_mla:
        moe = cfg.is_moe
        if moe and cfg.first_k_dense:
            prefix_caches = []
            for bp in params["dense_prefix"]:
                x, kv, _, _ = _attn_block(cfg, bp, x, positions)
                prefix_caches.append(kv)
        def body(carry, bp):
            x = carry
            x, kv, _, _ = _attn_block(cfg, bp, x, positions,
                                      parallel=parallel, moe=moe,
                                      moe_pool=params.get("moe_pool"))
            c, kr = kv
            return x, (pad_to(c, max_len, 1), pad_to(kr, max_len, 1))
        x, (cs, krs) = jax.lax.scan(body, x, params["blocks"])
        if cfg.first_k_dense:
            pc = jnp.stack([pad_to(c, max_len, 1) for c, _ in prefix_caches])
            pk = jnp.stack([pad_to(kr, max_len, 1) for _, kr in prefix_caches])
            cs = jnp.concatenate([pc, cs], axis=0)
            krs = jnp.concatenate([pk, krs], axis=0)
        cache = {"c": cs, "kr": krs}
    else:
        moe = cfg.is_moe
        img = batch.get("image_embeds")
        eff = cache["k"].shape[2]

        def body(carry, bp):
            x, aux = carry
            x, kv, _, a = _attn_block(cfg, bp, x, positions,
                                      parallel=parallel, moe=moe,
                                      moe_pool=params.get("moe_pool"))
            k, v = kv
            return (x, aux + a), (pad_to(k[:, -eff:], eff, 1),
                                  pad_to(v[:, -eff:], eff, 1))
        if cfg.arch_type == "vlm":
            every = cfg.cross_attn_every
            ng = cfg.num_layers // every
            blocks = jax.tree.map(
                lambda t: t.reshape(ng, every - 1, *t.shape[1:]),
                params["blocks"])

            def group(carry, bps):
                x = carry
                bcross, bselfs = bps
                x, kvc, imgkv, _ = _attn_block(cfg, bcross, x, positions,
                                               image_x=img)
                kc, vc = kvc
                def inner(x, bp):
                    x, kv, _, _ = _attn_block(cfg, bp, x, positions)
                    return x, kv
                x, (ks, vs) = jax.lax.scan(inner, x, bselfs)
                ks = jnp.concatenate([kc[None], ks], 0)   # [every, B, S, ...]
                vs = jnp.concatenate([vc[None], vs], 0)
                return x, (pad_to(ks, max_len, 2), pad_to(vs, max_len, 2),
                           imgkv[0], imgkv[1])
            x, (ks, vs, imk, imv) = jax.lax.scan(group, x,
                                                 (params["cross_blocks"],
                                                  blocks))
            cache = {"k": ks.reshape(-1, B, max_len, *ks.shape[4:]),
                     "v": vs.reshape(-1, B, max_len, *vs.shape[4:]),
                     "img_k": imk, "img_v": imv}
        else:
            (x, _), (ks, vs) = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                            params["blocks"])
            cache = {"k": ks, "v": vs}

    x = apply_norm(params["final_norm"], x, cfg.norm_type)
    lengths = batch.get("lengths")
    if lengths is None:
        last = x[:, -1]
    else:
        last = x[jnp.arange(B), lengths - 1]
    logits = linear(params["lm_head"], last)
    return logits, cache


# ------------------------------------------------------------------- decode

def decode_step(cfg: ModelConfig, params: Params, tokens, cache, lengths,
                *, parallel=None, collect_routing=False):
    """One decode step.  tokens [B,1]; lengths [B] = number of tokens already
    in the cache (the new token is written at slot ``lengths``).
    Returns (logits [B,V], cache') — plus per-layer routing counts
    [L_moe, E] when ``collect_routing`` (gated on
    :func:`routing_stats_supported`)."""
    assert cfg.has_decode
    if collect_routing:
        assert routing_stats_supported(cfg), \
            f"{cfg.name}: routing telemetry unsupported"
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = lengths[:, None]
    write_pos = _cache_slot(cfg, lengths)
    valid = lengths + 1

    if cfg.arch_type == "ssm":
        def body(x, inp):
            bp, c = inp
            x, c2 = _ssm_block(cfg, bp, x, cache=c)
            return x, c2
        x, new = jax.lax.scan(body, x, (params["blocks"], cache))
        new_cache = new
    elif cfg.arch_type == "hybrid":
        ng = cfg.num_layers // cfg.attn_every
        blocks = jax.tree.map(
            lambda t: t.reshape(ng, cfg.attn_every, *t.shape[1:]),
            params["blocks"])
        ssm_cache = jax.tree.map(
            lambda t: t.reshape(ng, cfg.attn_every, *t.shape[1:]),
            {"conv": cache["conv"], "state": cache["state"]})
        shared = params["shared_attn"]
        win = cache["attn_k"].shape[2]

        def group(x, inp):
            bps, sc, k, v = inp
            wp = lengths % win
            x, kv, _, _ = _attn_block(cfg, shared, x, positions, cache=(k, v),
                                      write_pos=wp,
                                      kv_valid_len=jnp.minimum(valid, win))
            def inner(x, inp2):
                bp, c = inp2
                x, c2 = _ssm_block(cfg, bp, x, cache=c)
                return x, c2
            x, sc2 = jax.lax.scan(inner, x, (bps, sc))
            return x, (sc2, kv[0], kv[1])
        x, (sc2, ks, vs) = jax.lax.scan(
            group, x, (blocks, ssm_cache, cache["attn_k"], cache["attn_v"]))
        new_cache = {
            "conv": sc2["conv"].reshape(cfg.num_layers, *sc2["conv"].shape[2:]),
            "state": sc2["state"].reshape(cfg.num_layers,
                                          *sc2["state"].shape[2:]),
            "attn_k": ks, "attn_v": vs}
    elif cfg.use_mla:
        moe = cfg.is_moe
        nk = cfg.first_k_dense
        cs, krs = cache["c"], cache["kr"]
        new_c, new_kr = [], []
        for i in range(nk):
            x, kv, _, _ = _attn_block(cfg, params["dense_prefix"][i], x,
                                      positions, cache=(cs[i], krs[i]),
                                      write_pos=write_pos, kv_valid_len=valid)
            new_c.append(kv[0]); new_kr.append(kv[1])
        def body(carry, inp):
            x = carry
            bp, c, kr = inp
            x, kv, _, _ = _attn_block(cfg, bp, x, positions, cache=(c, kr),
                                      write_pos=write_pos, kv_valid_len=valid,
                                      parallel=parallel, moe=moe,
                                      moe_pool=params.get("moe_pool"))
            return x, (kv[0], kv[1])
        x, (cs2, krs2) = jax.lax.scan(body, x,
                                      (params["blocks"], cs[nk:], krs[nk:]))
        if nk:
            cs2 = jnp.concatenate([jnp.stack(new_c), cs2], 0)
            krs2 = jnp.concatenate([jnp.stack(new_kr), krs2], 0)
        new_cache = {"c": cs2, "kr": krs2}
    else:
        moe = cfg.is_moe
        win = cache["k"].shape[2]
        wp = write_pos if cfg.attn_window is None else lengths % win
        vl = valid if cfg.attn_window is None else jnp.minimum(valid, win)

        if cfg.arch_type == "vlm":
            every = cfg.cross_attn_every
            ng = cfg.num_layers // every
            # per group: row 0 = cross layer's self-attn kv, rows 1.. = self
            ks = cache["k"].reshape(ng, every, *cache["k"].shape[1:])
            vs = cache["v"].reshape(ng, every, *cache["v"].shape[1:])
            blocks = jax.tree.map(
                lambda t: t.reshape(ng, every - 1, *t.shape[1:]),
                params["blocks"])

            def group(x, inp):
                bcross, bselfs, kg, vg, ik, iv = inp
                x, kv0, _, _ = _attn_block(cfg, bcross, x, positions,
                                           cache=(kg[0], vg[0]), write_pos=wp,
                                           kv_valid_len=vl, image_kv=(ik, iv))
                def inner(carry, inp2):
                    x = carry
                    bp, k, v = inp2
                    x, kv, _, _ = _attn_block(cfg, bp, x, positions,
                                              cache=(k, v), write_pos=wp,
                                              kv_valid_len=vl)
                    return x, (kv[0], kv[1])
                x, (ks2, vs2) = jax.lax.scan(inner, x, (bselfs, kg[1:], vg[1:]))
                return x, (jnp.concatenate([kv0[0][None], ks2], 0),
                           jnp.concatenate([kv0[1][None], vs2], 0))
            x, (ks2, vs2) = jax.lax.scan(
                group, x, (params["cross_blocks"], blocks, ks, vs,
                           cache["img_k"], cache["img_v"]))
            new_cache = {"k": ks2.reshape(-1, *ks2.shape[2:]),
                         "v": vs2.reshape(-1, *vs2.shape[2:]),
                         "img_k": cache["img_k"], "img_v": cache["img_v"]}
        else:
            def body(carry, inp):
                x = carry
                bp, k, v = inp
                out = _attn_block(cfg, bp, x, positions, cache=(k, v),
                                  write_pos=wp, kv_valid_len=vl,
                                  parallel=parallel, moe=moe,
                                  moe_pool=params.get("moe_pool"),
                                  collect_routing=collect_routing)
                if collect_routing:
                    x, kv, _, _, cnt = out
                    return x, (kv[0], kv[1], cnt)
                x, kv, _, _ = out
                return x, (kv[0], kv[1])
            x, scanned = jax.lax.scan(body, x,
                                      (params["blocks"], cache["k"],
                                       cache["v"]))
            if collect_routing:
                ks2, vs2, routed_counts = scanned
            else:
                ks2, vs2 = scanned
            new_cache = {"k": ks2, "v": vs2}

    x = apply_norm(params["final_norm"], x, cfg.norm_type)
    logits = linear(params["lm_head"], x[:, 0])
    if collect_routing:
        return logits, new_cache, routed_counts
    return logits, new_cache
