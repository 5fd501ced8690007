"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_gmm_ref(table, pool, x):
    """out[e] = x[e] @ pool[table[e]]."""
    w = pool[table]                                   # [E_local, D, F]
    return jnp.einsum("ecd,edf->ecf", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def paged_expert_ffn_ref(table_i, table_g, table_o, pool_i, pool_g, pool_o, x):
    h = paged_gmm_ref(table_i, pool_i, x)
    g = paged_gmm_ref(table_g, pool_g, x)
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    return paged_gmm_ref(table_o, pool_o, h)


def quant_paged_gmm_ref(table, pool, scales, x):
    """Dequant-then-delegate oracle for the int8 paged GMM: pool int8
    [n_pages, D, F], scales f32 [n_pages] (one per page)."""
    from repro.kernels.quant import dequantize_rows
    w = dequantize_rows(pool, scales, (-2, -1))
    return paged_gmm_ref(table, w, x.astype(jnp.float32)).astype(x.dtype)


def quant_paged_expert_ffn_ref(table_i, table_g, table_o, pool_i, pool_g,
                               pool_o, scale_i, scale_g, scale_o, x):
    h = quant_paged_gmm_ref(table_i, pool_i, scale_i, x)
    g = quant_paged_gmm_ref(table_g, pool_g, scale_g, x)
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    return quant_paged_gmm_ref(table_o, pool_o, scale_o, h)


def flash_attention_ref(q, k, v, causal=True):
    """q [B,S,H,hd]; k/v [B,S,KVH,hd]."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, S, KVH, G, hd)
    s = jnp.einsum("bqkgh,btkh->bkgqt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd).astype(q.dtype)


def paged_decode_attention_ref(q, k_cache, v_cache, lengths):
    """q [B,H,hd]; caches [B,S,KVH,hd]; lengths [B]."""
    B, H, hd = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(hd)
    t = jnp.arange(S)[None, None, None]
    s = jnp.where(t < lengths[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(q.dtype)


def _at_layer(layer, *pools):
    """One layer of stacked pools [L, NB, ...]; ``layer=None`` means the
    pools already are one layer's [NB, ...]."""
    return pools if layer is None else tuple(p[layer] for p in pools)


def block_paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                                     layer=None):
    """Block-table paged decode: q [B,H,hd]; k/v_pool [NB,bs,KVH,hd], or
    [L,NB,bs,KVH,hd] read at ``layer``; block_tables [B,MB] (pool indices;
    entries past a sequence's length are don't-care); lengths [B] ->
    [B,H,hd].

    Gathers each sequence's K/V through its block table into a contiguous
    [B, MB*bs, KVH, hd] view, then runs the dense masked decode attention —
    the oracle the Pallas kernel (and the engine's CPU fallback) must match.
    """
    k_pool, v_pool = _at_layer(layer, k_pool, v_pool)
    B, H, hd = q.shape
    bs, KVH = k_pool.shape[1], k_pool.shape[2]
    MB = block_tables.shape[1]
    k = k_pool[block_tables].reshape(B, MB * bs, KVH, hd)
    v = v_pool[block_tables].reshape(B, MB * bs, KVH, hd)
    return paged_decode_attention_ref(q, k, v, lengths)


def quant_block_paged_decode_attention_ref(q, k_pool, k_scale, v_pool,
                                           v_scale, block_tables, lengths,
                                           layer=None):
    """Dequant-then-delegate oracle for the int8 block-table paged decode:
    k/v_pool int8 [NB,bs,KVH,hd], k/v_scale f32 [NB,bs] (one per token row,
    ``quantize_rows`` over (KVH, hd)); stacked [L, ...] read at ``layer``."""
    from repro.kernels.quant import dequantize_rows
    k_pool, k_scale, v_pool, v_scale = _at_layer(layer, k_pool, k_scale,
                                                 v_pool, v_scale)
    k = dequantize_rows(k_pool, k_scale, (-2, -1))
    v = dequantize_rows(v_pool, v_scale, (-2, -1))
    return block_paged_decode_attention_ref(q, k, v, block_tables, lengths)


def mixed_block_paged_attention_ref(q, k_pool, v_pool, block_tables,
                                    ctx_lens, q_lens, layer=None):
    """Mixed chunked-prefill / decode attention over the block pool
    (pools as in :func:`block_paged_decode_attention_ref`).

    q [B,Sq,H,hd]: row ``i`` of sequence ``b`` is the query at absolute
    position ``ctx_lens[b] - q_lens[b] + i`` — a prefill chunk is the last
    ``q_lens[b]`` tokens of a context of ``ctx_lens[b]`` tokens whose K/V
    (including the chunk's own) already sit in the pool.  ``q_lens[b] == 1``
    degenerates to plain paged decode.  Rows ``i >= q_lens[b]`` are padding;
    they attend over the full context (mask ``t < ctx``) so the output is
    deterministic, but callers discard them.  Sentinel block-table entries
    (``NB``) are clamped in-bounds; position masking keeps them inert.
    """
    k_pool, v_pool = _at_layer(layer, k_pool, v_pool)
    B, Sq, H, hd = q.shape
    NB, bs, KVH = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    MB = block_tables.shape[1]
    G = H // KVH
    bt = jnp.minimum(block_tables, NB - 1)
    k = k_pool[bt].reshape(B, MB * bs, KVH, hd)
    v = v_pool[bt].reshape(B, MB * bs, KVH, hd)
    qg = q.reshape(B, Sq, KVH, G, hd)
    s = jnp.einsum("bqkgh,btkh->bkgqt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    q_abs = (ctx_lens - q_lens)[:, None] + jnp.arange(Sq)[None]     # [B,Sq]
    t = jnp.arange(MB * bs)
    mask = (t[None, None, None, None, :] < ctx_lens[:, None, None, None, None]) \
        & (t[None, None, None, None, :] <= q_abs[:, None, None, :, None])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def quant_mixed_block_paged_attention_ref(q, k_pool, k_scale, v_pool,
                                          v_scale, block_tables, ctx_lens,
                                          q_lens, layer=None):
    """Dequant-then-delegate oracle for the int8 mixed prefill/decode
    attention (same scale layout as the quant block-decode oracle)."""
    from repro.kernels.quant import dequantize_rows
    k_pool, k_scale, v_pool, v_scale = _at_layer(layer, k_pool, k_scale,
                                                 v_pool, v_scale)
    k = dequantize_rows(k_pool, k_scale, (-2, -1))
    v = dequantize_rows(v_pool, v_scale, (-2, -1))
    return mixed_block_paged_attention_ref(q, k, v, block_tables, ctx_lens,
                                           q_lens)


def ssd_scan_ref(x, dt, A, Bm, Cm):
    """Sequential (exact) SSD recurrence.  x [B,S,H,P], dt [B,S,H], A [H],
    Bm/Cm [B,S,N] -> (y [B,S,H,P] f32, state [B,H,N,P] f32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp            # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(dtt * A[None])   # [B,H]
        state = state * decay[..., None, None] + jnp.einsum(
            "bn,bhp,bh->bhnp", bt, xt.astype(jnp.float32), dtt)
        y = jnp.einsum("bn,bhnp->bhp", ct, state)
        return state, y

    state0 = jnp.zeros((B, H, N, P), jnp.float32)
    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32),
          Bm.transpose(1, 0, 2).astype(jnp.float32),
          Cm.transpose(1, 0, 2).astype(jnp.float32))
    state, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3), state


def mla_decode_attention_ref(q_eff, q_rope, c_cache, kr_cache, lengths):
    """Absorbed MLA decode: q_eff [B,H,r], q_rope [B,H,dr],
    c_cache [B,S,r], kr_cache [B,S,dr], lengths [B] -> [B,H,r]."""
    r, dr = q_eff.shape[-1], q_rope.shape[-1]
    qk_dim = (128 if r >= 128 else r) + dr
    s = (jnp.einsum("bhr,btr->bht", q_eff.astype(jnp.float32),
                    c_cache.astype(jnp.float32))
         + jnp.einsum("bhd,btd->bht", q_rope.astype(jnp.float32),
                      kr_cache.astype(jnp.float32))) / math.sqrt(qk_dim)
    t = jnp.arange(c_cache.shape[1])[None, None]
    s = jnp.where(t < lengths[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btr->bhr", p,
                      c_cache.astype(jnp.float32)).astype(q_eff.dtype)


def kv_cache_write_ref(cache, new_kv, pos):
    b = jnp.arange(cache.shape[0])
    return cache.at[b, pos].set(new_kv.astype(cache.dtype))
