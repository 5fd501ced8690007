"""Attention over the block-paged KV pool (Pallas TPU) — one kernel family.

The PagedAttention layout: one shared block *pool* per layer, stacked over
the model's layers as ``[L, NB, bs, KVH, hd]``, plus per-sequence block
tables ``[B, MB]`` (``serving/kv_blocks.py``).  The block table and the
layer index ride the scalar prefetch: each kv BlockSpec index_map picks
pool layer ``layer`` and dereferences ``table[b, ki]`` to pick the physical
block, so the kernel reads exactly the blocks a sequence owns, straight out
of the stacked pool — no per-layer slice of the pool is ever materialised
(a Mosaic call cannot fuse one, so XLA would copy the layer out).
Non-contiguous pool rows appear contiguous to the softmax, the
kernel-level zero-copy-remap guarantee (permuting pool rows + tables is a
no-op, asserted in tests).  A caller holding one layer's pool
``[NB, bs, KVH, hd]`` passes no layer: it is read as a stack of one.

One kernel serves every query shape: query row ``i`` of sequence ``b``
attends causally from absolute position ``ctx[b] - q_len[b] + i``.  A
chunked-prefill chunk is ``q_len`` rows; decode is ``Sq == q_len == 1``
(``block_paged_decode_attention``); the slot-contiguous cache
``[B, S_max, KVH, hd]`` is the same pool viewed with an identity table
(``paged_decode_attention``).  int8 pools fuse the per-token dequant; their
f32 scale pools ``[L, NB, bs]`` are read through the same layer and table.

Grid ``(B, Sq/bq, MB)``.  Each step streams one whole KV block with all
``KVH`` heads — ``(1, bs, KVH, hd)`` of one layer — because Mosaic requires
the last two block dims to be full or (8, 128)-aligned, which a one-head
``(…, 1, hd)`` block is not.  The query tile ``(1, bq, KVH, G, hd)`` holds
every head too; the body loops over heads with an online softmax in VMEM
scratch.  Blocks beyond a sequence's context, and blocks past the query
tile's last causal position, are skipped: their grid steps repeat the last
live block's index, so Pallas issues no fetch, and the body computes
nothing.  A decode row thus reads ``ceil(len/bs)`` blocks, not ``MB``.  The
query tiles of one prefill chunk each stream the context up to their own
causal frontier, so a chunk of ``n`` tiles reads the shared prefix ``n``
times.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows of a prefill chunk per grid step: 32 rows x 8 grouped heads
# fill 256 MXU rows per KV head at qwen3-30b-a3b's 32/4 heads
CHUNK_TILE = 32


def _kernel(ctx_ref, qlen_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
            scale, bs, n_k, bq, G, KVH, quant):
    # block table and layer are consumed by the BlockSpec index maps
    del bt_ref, layer_ref
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    qt = pl.program_id(1)
    ki = pl.program_id(2)
    R = bq * G                                   # query rows per kv head

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    # absolute position of this tile's first query row; padding rows
    # (i >= q_len) degrade to full-context decode masking so every row keeps
    # a sane softmax (block 0 is always live: ctx >= 1)
    q0 = ctx - qlen_ref[b] + qt * bq

    @pl.when((ki * bs < ctx) & (ki * bs <= q0 + bq - 1))
    def _step():
        pos = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        q_abs = q0 + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 0) // G
        valid = (pos < ctx) & (pos <= q_abs)
        if quant:
            sk = ks_ref[0]                                   # [1, bs] f32
            sv = vs_ref[0]
        for h in range(KVH):
            rows = pl.ds(h * R, R)
            q = q_ref[0, :, h].astype(jnp.float32).reshape(R, -1)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # [bs, hd]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            # per-token k-dequant commutes out of the q.k^T contraction:
            # column-scale the scores instead of materializing a dequant tile
            s = (s * sk if quant else s) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[rows]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
            # v-dequant folds into the probability rows the same way
            pv = p * sv if quant else p
            acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[rows] = m_cur

    @pl.when(ki == n_k - 1)
    def _done():
        for h in range(KVH):
            rows = pl.ds(h * R, R)
            o = acc_ref[rows] / jnp.maximum(l_ref[rows], 1e-30)
            o_ref[0, :, h] = o.reshape(bq, G, -1).astype(o_ref.dtype)


def _paged_attention(q, k_pool, v_pool, block_tables, ctx_lens, q_lens,
                     layer, k_scale=None, v_scale=None, *, block_q,
                     interpret):
    """q [B,Sq,H,hd]; pools [L,NB,bs,KVH,hd] (+ [L,NB,bs] f32 scales when
    int8), read at pool layer ``layer`` -> [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    L, NB, bs, KVH = k_pool.shape[:4]
    MB = block_tables.shape[1]
    G = H // KVH
    bq = block_q if Sq % block_q == 0 else Sq
    quant = k_scale is not None
    bt = jnp.minimum(block_tables.astype(jnp.int32), NB - 1)
    ly = jnp.clip(jnp.reshape(layer, (1,)).astype(jnp.int32), 0, L - 1)
    qg = q.reshape(B, Sq, KVH, G, hd)

    q_spec = pl.BlockSpec((1, bq, KVH, G, hd),
                          lambda b, t, ki, C, Q, BT, LY: (b, t, 0, 0, 0))

    def kv_block(b, t, ki, C, Q, BT, LY):
        # clamp to the tile's last live block (the kernel body's condition)
        last = jnp.minimum(C[b] - 1, C[b] - Q[b] + (t + 1) * bq - 1) // bs
        return BT[b, jnp.minimum(ki, last)]

    # the layer axis is squeezed: the body sees the same (1, bs, KVH, hd)
    # block as a single-layer pool would give
    kv_spec = pl.BlockSpec((None, 1, bs, KVH, hd),
                           lambda *g: (g[-1][0], kv_block(*g), 0, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, k_pool, v_pool]
    if quant:
        # [L, NB, 1, bs] so the scale block's last two dims are full dims;
        # the scales dereference the same prefetched layer and table as the
        # entries, so a remapped/migrated block always arrives with its own
        # scales
        s_spec = pl.BlockSpec((None, 1, 1, bs),
                              lambda *g: (g[-1][0], kv_block(*g), 0, 0))
        in_specs += [s_spec, s_spec]
        operands += [k_scale.astype(jnp.float32).reshape(L, NB, 1, bs),
                     v_scale.astype(jnp.float32).reshape(L, NB, 1, bs)]
    R = bq * G
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd), bs=bs, n_k=MB,
                          bq=bq, G=G, KVH=KVH, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, Sq // bq, MB),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((KVH * R, 1), jnp.float32),
                pltpu.VMEM((KVH * R, 1), jnp.float32),
                pltpu.VMEM((KVH * R, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(ctx_lens.astype(jnp.int32), q_lens.astype(jnp.int32), bt, ly,
      *operands)
    return out.reshape(B, Sq, H, hd)


def _stacked(layer, *pools):
    """(layer, *pools) as the kernel reads them: without a layer index the
    pools are one layer's ``[NB, ...]``, read as a stack of one."""
    if layer is None:
        return (0, *(p[None] for p in pools))
    return (layer, *pools)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mixed_block_paged_attention(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, block_tables: jax.Array,
                                ctx_lens: jax.Array, q_lens: jax.Array,
                                layer: jax.Array | None = None, *,
                                interpret: bool = False) -> jax.Array:
    """Mixed chunked-prefill / decode attention (continuous batching).

    q [B,Sq,H,hd]; k/v_pool [L,NB,bs,KVH,hd] read at pool layer ``layer``
    (or one layer's [NB,bs,KVH,hd] with ``layer=None``); block_tables
    [B,MB]; ctx_lens [B] (total context incl. the chunk, already written to
    the pool); q_lens [B] (valid chunk rows) -> [B,Sq,H,hd].

    Query row ``i`` of sequence ``b`` attends causally from absolute
    position ``ctx_lens[b] - q_lens[b] + i``; ``q_lens == 1`` is exactly
    paged decode, so one kernel serves interleaved prefill+decode buckets.
    Sentinel (``NB``) block-table rows are clamped in-bounds before the
    index_map dereference and position-masked inert; blocks at or beyond
    ``ctx_lens[b]`` are neither fetched nor computed.  Oracle:
    ``ref.mixed_block_paged_attention_ref``.
    """
    layer, k_pool, v_pool = _stacked(layer, k_pool, v_pool)
    return _paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                            q_lens, layer, block_q=CHUNK_TILE,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_mixed_block_paged_attention(q: jax.Array, k_pool: jax.Array,
                                      k_scale: jax.Array, v_pool: jax.Array,
                                      v_scale: jax.Array,
                                      block_tables: jax.Array,
                                      ctx_lens: jax.Array,
                                      q_lens: jax.Array,
                                      layer: jax.Array | None = None, *,
                                      interpret: bool = False) -> jax.Array:
    """Int8 variant of ``mixed_block_paged_attention``: same masks and mixed
    prefill/decode semantics, int8 k/v pools with [L, NB, bs] (or one
    layer's [NB, bs]) f32 per-token scale pools riding the prefetched layer
    and block table.  Oracle: ``ref.quant_mixed_block_paged_attention_ref``.
    """
    layer, k_pool, k_scale, v_pool, v_scale = _stacked(
        layer, k_pool, k_scale, v_pool, v_scale)
    return _paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                            q_lens, layer, k_scale, v_scale,
                            block_q=CHUNK_TILE, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, block_tables: jax.Array,
                                 lengths: jax.Array,
                                 layer: jax.Array | None = None, *,
                                 interpret: bool = False) -> jax.Array:
    """q [B,H,hd]; k/v_pool [L,NB,bs,KVH,hd] read at pool layer ``layer``
    (or one layer's [NB,bs,KVH,hd] with ``layer=None``); block_tables
    [B,MB] int32; lengths [B] -> [B,H,hd].  kv tile ``ki`` of sequence
    ``b`` is pool row ``block_tables[b, ki]`` — dereferenced in the
    BlockSpec index_map via scalar prefetch, so only owned blocks are
    streamed from HBM (and none at all beyond ``lengths[b]``).  The mixed
    kernel at one query row."""
    layer, k_pool, v_pool = _stacked(layer, k_pool, v_pool)
    ones = jnp.ones_like(lengths)
    return _paged_attention(q[:, None], k_pool, v_pool, block_tables,
                            lengths, ones, layer, block_q=1,
                            interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_block_paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                                       k_scale: jax.Array,
                                       v_pool: jax.Array,
                                       v_scale: jax.Array,
                                       block_tables: jax.Array,
                                       lengths: jax.Array,
                                       layer: jax.Array | None = None, *,
                                       interpret: bool = False) -> jax.Array:
    """Int8 variant of ``block_paged_decode_attention``: k/v_pool are int8
    ``[L, NB, bs, KVH, hd]`` and k/v_scale the per-token f32 scale pools
    ``[L, NB, bs]`` (``kernels.quant.quantize_rows`` over ``(KVH, hd)``),
    or one layer's of each with ``layer=None``.  Dequant is fused into the
    softmax at ~half the HBM traffic of the f32 path.  Oracle:
    ``ref.quant_block_paged_decode_attention_ref``."""
    layer, k_pool, k_scale, v_pool, v_scale = _stacked(
        layer, k_pool, k_scale, v_pool, v_scale)
    ones = jnp.ones_like(lengths)
    return _paged_attention(q[:, None], k_pool, v_pool, block_tables,
                            lengths, ones, layer, k_scale, v_scale,
                            block_q=1, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def paged_decode_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, lengths: jax.Array, *,
                           block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """Slot-contiguous cache: q [B,H,hd]; k/v_cache [B,S_max,KVH,hd];
    lengths [B] -> [B,H,hd].  The cache is viewed as a pool of
    ``block_k``-token blocks with an identity block table."""
    B, S_max, KVH, hd = k_cache.shape
    bk = min(block_k, S_max)
    assert S_max % bk == 0
    n_k = S_max // bk
    bt = jnp.arange(B * n_k, dtype=jnp.int32).reshape(B, n_k)
    as_pool = lambda c: c.reshape(B * n_k, bk, KVH, hd)
    return block_paged_decode_attention(q, as_pool(k_cache),
                                        as_pool(v_cache), bt, lengths,
                                        interpret=interpret)
