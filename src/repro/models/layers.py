"""Core transformer layers as pure functions over parameter pytrees.

Conventions
-----------
* params are nested dicts of jnp arrays; init fns take an rng and return them.
* activations: [B, S, D]; attention heads materialized as [B, S, H, hd].
* KV caches: {'k': [B, S_max, KVH, hd], 'v': ...}; the valid length / write
  index is passed explicitly (the serving engine owns it).
* all matmuls accumulate in float32 (``preferred_element_type``) — bf16 params
  with f32 accumulation is the TPU-native convention.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------- utils

def dot(x, w):
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def linear_init(rng, d_in, d_out, dtype, bias=False, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(rng, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = dot(x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


# --------------------------------------------------------------------- norms

def norm_init(d, norm_type, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p, x, norm_type, eps=1e-5):
    xf = x.astype(jnp.float32)
    if norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------- rope

def rope_tables(positions, rot_dim, base=10000.0):
    """positions [..., S] -> cos,sin [..., S, rot_dim/2]."""
    inv = 1.0 / (base ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, rot_dim):
    """x [B,S,H,hd]; rotary applied to the first ``rot_dim`` dims (pairwise)."""
    if rot_dim == 0:
        return x
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    x1 = xr[..., 0::2]
    x2 = xr[..., 1::2]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    xr = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([xr, xp], axis=-1).astype(x.dtype) if rot_dim < x.shape[-1] else xr.astype(x.dtype)


# ----------------------------------------------------------------- attention

def _mha_block(q, k, v, *, q_pos, kv_pos, causal, window, kv_valid_len):
    """One dense attention block.

    q [B,Sq,KVH,G,hd], k/v [B,Skv,KVH,hd]; positions are int arrays [B,Sq]/[B,Skv].
    Returns [B,Sq,KVH,G,hd].
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqkgh,btkh->bkgqt", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.ones(scores.shape[-2:], bool)[None, None, None]
    dq = q_pos[:, None, None, :, None]
    dk = kv_pos[:, None, None, None, :]
    if causal:
        mask = mask & (dk <= dq)
    if window is not None:
        mask = mask & (dq - dk < window)
    if kv_valid_len is not None:
        mask = mask & (dk < kv_valid_len[:, None, None, None, None])
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,btkh->bqkgh", p, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def mha(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
        kv_valid_len=None, q_chunk=1024):
    """Grouped-query attention with q-chunking (keeps the [Sq,Skv] score
    matrix bounded — the memory-roofline-friendly formulation).

    q [B,Sq,H,hd], k/v [B,Skv,KVH,hd] -> [B,Sq,H,hd]
    """
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, hd)
    if Sq <= q_chunk:
        out = _mha_block(qg, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal,
                         window=window, kv_valid_len=kv_valid_len)
        return out.reshape(B, Sq, H, hd)

    n = Sq // q_chunk
    assert Sq % q_chunk == 0, f"Sq={Sq} not divisible by q_chunk={q_chunk}"
    qs = qg.reshape(B, n, q_chunk, KVH, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ps = q_pos.reshape(B, n, q_chunk).transpose(1, 0, 2)

    def body(_, qc_pc):
        qc, pc = qc_pc
        o = _mha_block(qc, k, v, q_pos=pc, kv_pos=kv_pos, causal=causal,
                       window=window, kv_valid_len=kv_valid_len)
        return None, o

    _, outs = jax.lax.scan(body, None, (qs, ps))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KVH, G, hd)
    return out.reshape(B, Sq, H, hd)


def attention_init(rng, cfg, dtype):
    D = cfg.d_model
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    return {
        "q": linear_init(ks[0], D, H * hd, dtype, bias=cfg.qkv_bias),
        "k": linear_init(ks[1], D, KVH * hd, dtype, bias=cfg.qkv_bias),
        "v": linear_init(ks[2], D, KVH * hd, dtype, bias=cfg.qkv_bias),
        "o": linear_init(ks[3], H * hd, D, dtype),
    }


def attention_apply(cfg, p, x, positions, *, cache=None, write_pos=None,
                    kv_valid_len=None, kv_x=None, causal=None, window=None,
                    rope=True):
    """Self- or cross-attention with optional KV cache.

    * forward/prefill: cache=None -> uses computed k/v; returns (y, (k, v)).
    * decode: cache=(k_cache, v_cache), write_pos [B] int32 -> writes the new
      kv of each sequence at its own slot and attends over the cache;
      returns (y, cache').
    * cross-attention: kv_x = encoder states (no rope on kv, not causal).
    """
    B, Sq, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    causal = cfg.causal if causal is None else causal
    window = cfg.attn_window if window is None else window

    q = linear(p["q"], x).reshape(B, Sq, H, hd)
    src = x if kv_x is None else kv_x
    k = linear(p["k"], src).reshape(B, src.shape[1], KVH, hd)
    v = linear(p["v"], src).reshape(B, src.shape[1], KVH, hd)

    rot_dim = int(cfg.resolved_head_dim * cfg.rope_fraction) // 2 * 2
    if rope and rot_dim and kv_x is None:
        cos_q, sin_q = rope_tables(positions, rot_dim)
        q = apply_rope(q, cos_q, sin_q, rot_dim)
        k = apply_rope(k, cos_q, sin_q, rot_dim)

    if cache is not None:
        k_cache, v_cache = cache
        if write_pos is not None:
            b_idx = jnp.arange(B)
            k_cache = k_cache.at[b_idx, write_pos].set(
                k[:, 0].astype(k_cache.dtype), mode="drop")
            v_cache = v_cache.at[b_idx, write_pos].set(
                v[:, 0].astype(v_cache.dtype), mode="drop")
        k, v = k_cache, v_cache
        kv_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (B, k.shape[1]))
        new_cache = (k_cache, v_cache)
    else:
        kv_pos = (positions if kv_x is None else
                  jnp.broadcast_to(jnp.arange(src.shape[1])[None],
                                   (B, src.shape[1])))
        new_cache = (k, v)

    y = mha(q, k, v, q_pos=positions, kv_pos=kv_pos, causal=causal,
            window=window, kv_valid_len=kv_valid_len)
    return linear(p["o"], y.reshape(B, Sq, H * hd)), new_cache


def paged_attend(parallel, attend, q, pools, layer, table, ctx_lens, *rest):
    """``attend(q, *pools, table, ctx_lens, *rest, layer=layer)`` over the
    paged pool.

    ``pools``: the stacked KV pool leaves ``[L, NB, ...]``, block axis 1,
    read at pool layer ``layer`` (a scalar); ``table`` [B,MB] global block
    ids, ``NB`` marking padding.  Without a multi-device mesh the table is
    clamped in-bounds and ``attend`` runs once.  A Mosaic kernel cannot be
    partitioned automatically, so on a mesh ``attend`` runs once per KV
    partition: the pool's block axis is sharded over the data-parallel axes
    of ``parallel``, one partition per replica, and every block of a
    sequence lives in its replica's partition (serving/kv_blocks.py;
    migration re-homes blocks before their slot moves).  Inside the
    ``shard_map`` the table is rebased onto the local partition; the layer
    index is replicated.  A batch that divides over the replicas is split
    across them, row ``b`` to replica ``b // (B / replicas)``; otherwise
    (one prefill chunk) every replica runs the whole batch against its own
    partition and the replica owning a row's first block keeps that row —
    the others' work is discarded.  A row with a live block outside its
    home partition comes out NaN, never a plausible wrong answer (a
    raising host callback would leave the other replicas waiting in the
    collective).
    """
    from jax.sharding import PartitionSpec as P

    nb = pools[0].shape[1]
    layer = jnp.asarray(layer, jnp.int32)
    mesh = getattr(parallel, "mesh", None)
    if mesh is None or mesh.size == 1:
        return attend(q, *pools, jnp.minimum(table, nb - 1), ctx_lens, *rest,
                      layer=layer)
    axes = tuple(a for a in parallel.dp_axes if a in mesh.axis_names)
    n = math.prod(mesh.shape[a] for a in axes)
    nb_local, bs = nb // n, pools[0].shape[2]
    split = q.shape[0] % n == 0

    def body(q, pools, layer, table, ctx_lens, *rest):
        local = table - jax.lax.axis_index(axes) * nb_local
        home = (local >= 0) & (local < nb_local)
        live = ((jnp.arange(table.shape[1]) < -(-ctx_lens[:, None] // bs))
                & (table < nb))
        owner = jnp.ones_like(home[:, 0]) if split else home[:, 0]
        foreign = jnp.any(live & ~home, axis=1) & owner
        out = attend(q, *pools, jnp.where(home, local, 0), ctx_lens, *rest,
                     layer=layer)
        per_row = (-1,) + (1,) * (out.ndim - 1)
        out = jnp.where(foreign.reshape(per_row), jnp.nan, out)
        if split:
            return out
        return jax.lax.psum(jnp.where(owner.reshape(per_row), out, 0), axes)

    row = P(axes) if split else P()
    specs = ((row, tuple(P(None, axes) for _ in pools), P(), row, row)
             + (row,) * len(rest))
    return jax.shard_map(body, mesh=mesh, in_specs=specs, out_specs=row,
                         check_vma=False)(q, tuple(pools), layer, table,
                                          ctx_lens, *rest)


def _write_pool(cache, layer, idx, k, v):
    """Scatter new K/V into pool layer ``layer`` of the stacked pool at
    ``idx`` (a tuple of index arrays over the block axis and beyond; ``NB``
    block ids drop).  The pool is the loop carry of the layer scan and is
    donated by the step, so this is a small in-place scatter.  A quantized
    pool quantizes per token row and scatters the scales alongside."""
    at = (layer, *idx)
    if "k_scale" not in cache:
        return {n: cache[n].at[at].set(new.astype(cache[n].dtype),
                                       mode="drop")
                for n, new in (("k", k), ("v", v))}
    from repro.kernels.quant import quantize_rows
    cache = dict(cache)
    for n, new in (("k", k), ("v", v)):
        qr, s = quantize_rows(new, (-2, -1))
        cache[n] = cache[n].at[at].set(qr, mode="drop")
        cache[n + "_scale"] = cache[n + "_scale"].at[at].set(s, mode="drop")
    return cache


def _attend_pool(parallel, kernel, quant_kernel, q, cache, layer, table,
                 ctx_lens, *rest):
    """Attention of ``q`` over pool layer ``layer`` of the stacked pool,
    through the int8 kernel (scales beside the entries) when the pool is
    quantized."""
    if "k_scale" in cache:
        return paged_attend(parallel, quant_kernel, q,
                            (cache["k"], cache["k_scale"], cache["v"],
                             cache["v_scale"]), layer, table, ctx_lens, *rest)
    return paged_attend(parallel, kernel, q, (cache["k"], cache["v"]), layer,
                        table, ctx_lens, *rest)


def paged_attention_apply(cfg, p, x, positions, *, cache, layer,
                          block_tables, write_block, lengths, parallel=None):
    """Decode-step attention over the paged KV pool (serving/kv_blocks.py).

    x [B,1,D]; ``cache`` = the whole stacked pool {'k','v':
    [L,NB,bs,KVH,hd]} — plus per-token f32 scale pools {'k_scale',
    'v_scale': [L,NB,bs]} when the pool is int8 (DESIGN.md §11) — of which
    this layer is pool layer ``layer``; block_tables [B,MB]; write_block
    [B] = pool row receiving this step's k/v (the engine guarantees it is
    uniquely owned — CoW happened before the step; entries == NB mark
    inactive slots and are dropped); lengths [B] = tokens already cached
    (the new token lands at offset ``lengths % bs``).  On a quantized pool
    the new token row is quantized at write time and attention runs through
    the fused-dequant kernel.  ``parallel``: the instance's ParallelCtx,
    whose data-parallel axes shard the pool (see :func:`paged_attend`).
    Returns (y [B,1,D], cache').
    """
    from repro.kernels import ops

    B, _, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    bs = cache["k"].shape[2]

    q = linear(p["q"], x).reshape(B, 1, H, hd)
    k = linear(p["k"], x).reshape(B, 1, KVH, hd)
    v = linear(p["v"], x).reshape(B, 1, KVH, hd)
    rot_dim = int(cfg.resolved_head_dim * cfg.rope_fraction) // 2 * 2
    if rot_dim:
        cos, sin = rope_tables(positions, rot_dim)
        q = apply_rope(q, cos, sin, rot_dim)
        k = apply_rope(k, cos, sin, rot_dim)

    cache = _write_pool(cache, layer, (write_block, lengths % bs),
                        k[:, 0], v[:, 0])
    o = _attend_pool(parallel, ops.block_paged_decode_attention,
                     ops.quant_block_paged_decode_attention, q[:, 0], cache,
                     layer, block_tables, lengths + 1)
    y = linear(p["o"], o.reshape(B, 1, H * hd))
    return y, cache


def paged_chunk_attention_apply(cfg, p, x, positions, *, cache, layer,
                                block_tables, chunk_block_ids, ctx_len, q_len,
                                parallel=None):
    """Chunked-prefill attention over the paged KV pool (one sequence).

    x [1,C,D] is one prefill chunk — the last ``q_len`` (<= C) of the
    sequence's first ``ctx_len`` tokens; ``positions`` [1,C] are their
    absolute positions.  ``cache`` and ``layer`` as in
    :func:`paged_attention_apply`.  The chunk's k/v are scattered into the
    pool rows ``chunk_block_ids`` [C/bs] of pool layer ``layer`` first
    (``NB`` marks padding beyond the prompt and CoW-shared prefix blocks —
    those writes drop; quantized pools quantize per token and scatter the
    scales alongside), then the chunk attends causally over the whole
    context through ``block_tables`` [1,MB] via the mixed prefill/decode
    kernel; ``parallel`` as in :func:`paged_attention_apply`.  Returns
    (y, cache').
    """
    from repro.kernels import ops

    B, C, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    bs = cache["k"].shape[2]

    q = linear(p["q"], x).reshape(B, C, H, hd)
    k = linear(p["k"], x).reshape(B, C, KVH, hd)
    v = linear(p["v"], x).reshape(B, C, KVH, hd)
    rot_dim = int(cfg.resolved_head_dim * cfg.rope_fraction) // 2 * 2
    if rot_dim:
        cos, sin = rope_tables(positions, rot_dim)
        q = apply_rope(q, cos, sin, rot_dim)
        k = apply_rope(k, cos, sin, rot_dim)

    # one row per token, as the decode write: a scatter of whole blocks
    # makes XLA pick another layout for the pool, and copy all of it to
    # and from the kernel's layout on every layer
    rows = (jnp.repeat(chunk_block_ids, bs), jnp.tile(jnp.arange(bs), C // bs))
    cache = _write_pool(cache, layer, rows, k[0], v[0])
    o = _attend_pool(parallel, ops.mixed_block_paged_attention,
                     ops.quant_mixed_block_paged_attention, q, cache, layer,
                     block_tables, jnp.reshape(ctx_len, (1,)),
                     jnp.reshape(q_len, (1,)))
    y = linear(p["o"], o.reshape(B, C, H * hd))
    return y, cache


def chunk_attention_apply(cfg, p, x, positions, *, k_row, v_row, start):
    """Chunked-prefill attention over a slot-contiguous dense cache row.

    x [1,C,D] is one prefill chunk at absolute positions ``positions``
    [1,C] (= start..start+C-1); k_row/v_row [1,S_max,KVH,hd] is the slot's
    cache row.  The chunk's k/v are written at [start, start+C) first, then
    the chunk attends causally over the row — position masking keeps stale
    rows beyond each query's position inert, exactly as monolithic prefill
    masks its padding.  Returns (y, (k_row', v_row')).
    """
    B, C, D = x.shape
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    S_max = k_row.shape[1]

    q = linear(p["q"], x).reshape(B, C, H, hd)
    k = linear(p["k"], x).reshape(B, C, KVH, hd)
    v = linear(p["v"], x).reshape(B, C, KVH, hd)
    rot_dim = int(cfg.resolved_head_dim * cfg.rope_fraction) // 2 * 2
    if rot_dim:
        cos, sin = rope_tables(positions, rot_dim)
        q = apply_rope(q, cos, sin, rot_dim)
        k = apply_rope(k, cos, sin, rot_dim)

    k_row = jax.lax.dynamic_update_slice(k_row, k.astype(k_row.dtype),
                                         (0, start, 0, 0))
    v_row = jax.lax.dynamic_update_slice(v_row, v.astype(v_row.dtype),
                                         (0, start, 0, 0))
    kv_pos = jnp.broadcast_to(jnp.arange(S_max)[None], (B, S_max))
    y = mha(q, k_row, v_row, q_pos=positions, kv_pos=kv_pos, causal=True,
            window=cfg.attn_window)
    return linear(p["o"], y.reshape(B, C, H * hd)), (k_row, v_row)


# ----------------------------------------------------------------------- mlp

def mlp_init(rng, d_model, d_ff, dtype, gated=True):
    ks = jax.random.split(rng, 3)
    p = {"up": linear_init(ks[0], d_model, d_ff, dtype),
         "down": linear_init(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["gate"] = linear_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp_apply(p, x, gated=True):
    h = linear(p["up"], x)
    if gated:
        h = h * jax.nn.silu(linear(p["gate"], x))
    else:
        h = jax.nn.gelu(h)
    return linear(p["down"], h)
