"""Jit'd public wrappers for the Pallas kernels.

Both choices below are made per call, from the backend JAX runs on, never
at import:

* ``interpret``: kernels run in the Pallas interpreter only on the CPU
  (the kernel body executes in Python, validating logic + BlockSpecs); on
  an accelerator they always compile to Mosaic.  A caller may still pass
  ``interpret=`` explicitly.
* ``impl`` (serving kernels): ``'auto'`` runs the jnp oracle from
  ``kernels/ref.py`` on the CPU — interpret-mode Pallas inside the
  per-layer decode scan is far slower than the gather, and the two are
  parity-tested in test_kernels.py — and the kernel everywhere else.
  ``'kernel'``/``'ref'`` force one side; ``REPRO_PAGED_IMPL`` (attention)
  and ``REPRO_POOLED_IMPL`` (expert FFN) set the default.
"""
from __future__ import annotations

import os

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_gmm import paged_expert_ffn as _ffn
from repro.kernels.moe_gmm import paged_gmm as _gmm
from repro.kernels.paged_attention import paged_decode_attention as _paged
from repro.kernels.ssd_scan import ssd_scan as _ssd

IMPL_ENV = ("REPRO_PAGED_IMPL", "REPRO_POOLED_IMPL")


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _interpret(kw) -> dict:
    kw.setdefault("interpret", on_cpu())
    return kw


def _use_ref(kw, env: str) -> bool:
    """Pop ``impl`` from ``kw`` and decide kernel vs oracle for this call."""
    impl = kw.pop("impl", None) or os.environ.get(env, "auto")
    if impl not in ("auto", "kernel", "ref"):
        raise ValueError(f"{env}={impl!r}: expected auto, kernel or ref")
    return impl == "ref" or (impl == "auto" and on_cpu())


def _tuned(kernel, kw, keys):
    """Fill block-size kwargs the caller left unset from the autotune
    best-config table (tools/autotune_kernels.py; no-op without a table)."""
    if all(k in kw for k in keys):
        return kw
    from repro.analysis.autotune import best_config
    best = best_config(kernel)
    for k in keys:
        if k in best:
            kw.setdefault(k, best[k])
    return kw


def paged_gmm(table, pool, x, **kw):
    _interpret(kw)
    _tuned("paged_gmm", kw, ("block_c", "block_f"))
    return _gmm(table, pool, x, **kw)


def quant_paged_gmm(table, pool, scales, x, **kw):
    """Int8 paged GMM (per-page f32 scales); impl as ``paged_expert_ffn``."""
    if _use_ref(kw, "REPRO_POOLED_IMPL"):
        from repro.kernels.ref import quant_paged_gmm_ref
        return quant_paged_gmm_ref(table, pool, scales, x)
    from repro.kernels.moe_gmm import quant_paged_gmm as _qgmm
    _interpret(kw)
    _tuned("paged_gmm", kw, ("block_c", "block_f"))
    return _qgmm(table, pool, scales, x, **kw)


def paged_expert_ffn(table_i, table_g, table_o, pool_i, pool_g, pool_o, x,
                     **kw):
    """Paged SwiGLU expert FFN (the pooled-expert serving hot path);
    ``impl``/``REPRO_POOLED_IMPL`` as in the module docstring."""
    if _use_ref(kw, "REPRO_POOLED_IMPL"):
        from repro.kernels.ref import paged_expert_ffn_ref
        return paged_expert_ffn_ref(table_i, table_g, table_o,
                                    pool_i, pool_g, pool_o, x)
    _interpret(kw)
    _tuned("paged_expert_ffn", kw, ("block_c", "block_f"))
    return _ffn(table_i, table_g, table_o, pool_i, pool_g, pool_o, x, **kw)


def quant_paged_expert_ffn(table_i, table_g, table_o, pool_i, pool_g, pool_o,
                           scale_i, scale_g, scale_o, x, **kw):
    """Int8 paged SwiGLU FFN (per-page, per-bank f32 scales)."""
    if _use_ref(kw, "REPRO_POOLED_IMPL"):
        from repro.kernels.ref import quant_paged_expert_ffn_ref
        return quant_paged_expert_ffn_ref(table_i, table_g, table_o,
                                          pool_i, pool_g, pool_o,
                                          scale_i, scale_g, scale_o, x)
    from repro.kernels.moe_gmm import quant_paged_expert_ffn as _qffn
    _interpret(kw)
    _tuned("paged_expert_ffn", kw, ("block_c", "block_f"))
    return _qffn(table_i, table_g, table_o, pool_i, pool_g, pool_o,
                 scale_i, scale_g, scale_o, x, **kw)


def flash_attention(q, k, v, **kw):
    _interpret(kw)
    return _flash(q, k, v, **kw)


def paged_decode_attention(q, k_cache, v_cache, lengths, **kw):
    _interpret(kw)
    _tuned("paged_decode_attention", kw, ("block_k",))
    return _paged(q, k_cache, v_cache, lengths, **kw)


def block_paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                                 layer=None, **kw):
    """Block-table paged decode (serving hot path; see kv_blocks.py) over
    stacked pools [L, NB, ...] read at ``layer``, or one layer's pools with
    ``layer=None``; ``impl``/``REPRO_PAGED_IMPL`` as in the module
    docstring.  On a mesh, ``models.layers.paged_attend`` runs it once per
    KV partition."""
    if _use_ref(kw, "REPRO_PAGED_IMPL"):
        from repro.kernels.ref import block_paged_decode_attention_ref
        return block_paged_decode_attention_ref(q, k_pool, v_pool,
                                                block_tables, lengths, layer)
    from repro.kernels.paged_attention import \
        block_paged_decode_attention as _block_paged
    return _block_paged(q, k_pool, v_pool, block_tables, lengths, layer,
                        **_interpret(kw))


def quant_block_paged_decode_attention(q, k_pool, k_scale, v_pool, v_scale,
                                       block_tables, lengths, layer=None,
                                       **kw):
    """Int8 block-table paged decode (per-token f32 scale pools riding the
    layer and block table)."""
    if _use_ref(kw, "REPRO_PAGED_IMPL"):
        from repro.kernels.ref import quant_block_paged_decode_attention_ref
        return quant_block_paged_decode_attention_ref(
            q, k_pool, k_scale, v_pool, v_scale, block_tables, lengths, layer)
    from repro.kernels.paged_attention import \
        quant_block_paged_decode_attention as _qblock
    return _qblock(q, k_pool, k_scale, v_pool, v_scale, block_tables, lengths,
                   layer, **_interpret(kw))


def mixed_block_paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                                q_lens, layer=None, **kw):
    """Mixed chunked-prefill / decode attention over the block pool (the
    continuous-batching hot path; see serving/scheduler.py); arguments as
    ``block_paged_decode_attention``."""
    if _use_ref(kw, "REPRO_PAGED_IMPL"):
        from repro.kernels.ref import mixed_block_paged_attention_ref
        return mixed_block_paged_attention_ref(q, k_pool, v_pool,
                                               block_tables, ctx_lens, q_lens,
                                               layer)
    from repro.kernels.paged_attention import \
        mixed_block_paged_attention as _mixed
    return _mixed(q, k_pool, v_pool, block_tables, ctx_lens, q_lens, layer,
                  **_interpret(kw))


def quant_mixed_block_paged_attention(q, k_pool, k_scale, v_pool, v_scale,
                                      block_tables, ctx_lens, q_lens,
                                      layer=None, **kw):
    """Int8 mixed chunked-prefill / decode attention."""
    if _use_ref(kw, "REPRO_PAGED_IMPL"):
        from repro.kernels.ref import quant_mixed_block_paged_attention_ref
        return quant_mixed_block_paged_attention_ref(
            q, k_pool, k_scale, v_pool, v_scale, block_tables, ctx_lens,
            q_lens, layer)
    from repro.kernels.paged_attention import \
        quant_mixed_block_paged_attention as _qmixed
    return _qmixed(q, k_pool, k_scale, v_pool, v_scale, block_tables,
                   ctx_lens, q_lens, layer, **_interpret(kw))


def ssd_scan(x, dt, A, Bm, Cm, **kw):
    _interpret(kw)
    return _ssd(x, dt, A, Bm, Cm, **kw)


def mla_decode_attention(q_eff, q_rope, c_cache, kr_cache, lengths, **kw):
    from repro.kernels.mla_decode import mla_decode_attention as _mla
    _interpret(kw)
    return _mla(q_eff, q_rope, c_cache, kr_cache, lengths, **kw)


def kv_cache_write(cache, new_kv, pos, **kw):
    from repro.kernels.kv_write import kv_cache_write as _kvw
    _interpret(kw)
    return _kvw(cache, new_kv, pos, **kw)
