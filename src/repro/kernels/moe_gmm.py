"""Paged grouped expert matmul (Pallas TPU).

This kernel is the *consumer* of the virtual expert page table
(core/expert_pages.py): expert weights live as non-contiguous pages in a
per-device pool, and the kernel addresses them **by index** via scalar
prefetch — the TPU-native realization of the paper's vpage-remap.  EP
reconfiguration only rewrites the (tiny) page table; no weight buffer is
ever reshaped or copied locally, and XLA never materializes a gathered
weight tensor.

Layout
------
pool   [n_pages, D, F]   physical pages, one expert's (wi|wg|wo) per page
table  [E_local]  int32  page index of each local expert (scalar prefetch)
x      [E_local, C, D]   dispatched tokens, grouped per expert
out    [E_local, C, F]

Grid: (E_local, C/bc, F/bf); the D contraction is unblocked (one MXU pass
per tile).  Block shapes default to MXU-aligned 128x128 tiles; VMEM per
step = bc*D + D*bf + bc*bf elements (~2.5 MB at D=2048, f32) << 16 MB v5e
VMEM.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(table_ref, x_ref, pool_ref, o_ref):
    # x_ref: [1, bc, D]; pool_ref: [1, D, bf] (page selected via index_map)
    x = x_ref[0]
    w = pool_ref[0]
    o_ref[0] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _quant_kernel(table_ref, x_ref, pool_ref, scale_ref, o_ref):
    # int8 page with one f32 scale per (page, bank): the per-page dequant
    # commutes out of the contraction — x @ (w_i8 * s) = (x @ w_i8) * s —
    # so the MXU streams int8 weights at half the HBM bytes and one scalar
    # multiply lands on the output tile.  scale_ref [E_local] in SMEM holds
    # each local expert's page scale (gathered through the same table).
    x = x_ref[0].astype(jnp.float32)
    w = pool_ref[0].astype(jnp.float32)
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (acc * scale_ref[pl.program_id(0)]).astype(o_ref.dtype)


def _clamp_block_f(dim: int, block: int) -> int:
    """The 'clamp' half of pad-or-clamp for the lane (minormost) dim, which
    the kernel cannot cheaply pad: largest multiple of 128 <= ``block`` that
    divides ``dim`` — Mosaic requires lane blocks to be 128-aligned — else
    the full dim (always legal, just a bigger VMEM tile)."""
    b = min(block, dim) - min(block, dim) % 128
    while b >= 128 and dim % b:
        b -= 128
    if b >= 128 and dim % b == 0:
        return b
    if block < dim:
        # the caller asked for a small lane tile but none divides dim: the
        # grid silently degrades to one full-width tile per step, multiplying
        # the VMEM working set by dim/block — surface the perf cliff instead
        # of hiding it (trace-time: block sizes are static)
        warnings.warn(
            f"paged_gmm: no 128-aligned block <= {block} divides F={dim}; "
            f"falling back to a full-width lane tile (VMEM working set "
            f"~{dim / max(block, 1):.1f}x the requested block). Pad F to a "
            f"multiple of 128 or pick block_f from its divisors.",
            stacklevel=3)
    return dim


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def paged_gmm(table: jax.Array, pool: jax.Array, x: jax.Array,
              *, block_c: int = 128, block_f: int = 128,
              interpret: bool = False) -> jax.Array:
    """out[e] = x[e] @ pool[table[e]] for each local expert e.

    Non-MXU-aligned shapes are handled pad-or-clamp: a token count ``C`` not
    divisible by ``block_c`` is zero-padded up to the next block (zero rows
    produce zero outputs, sliced off after the call — cheap: pads
    activations, never weights; the resulting ``bc`` is either 128-aligned
    or the full dim, both Mosaic-legal).  A hidden dim ``F`` not divisible
    by ``block_f`` instead *clamps* the block — to a 128-aligned divisor or
    the whole dim, never an unaligned lane tile — because padding F would
    mean copying every pool page.  Aliased tables — multiple entries naming
    the same page, the post-CoW sharing shape — are fine by construction:
    each grid step only reads ``pool[table[e]]``.
    """
    E_local, C, D = x.shape
    n_pages, D2, F = pool.shape
    assert D == D2, (D, D2)
    bc = min(block_c, C)
    if C % bc:
        C_pad = -(-C // bc) * bc
        x = jnp.pad(x, ((0, 0), (0, C_pad - C), (0, 0)))
    bf = _clamp_block_f(F, block_f)
    C_run = x.shape[1]

    grid = (E_local, C_run // bc, F // bf)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda e, i, j, tbl: (e, i, 0)),
                pl.BlockSpec((1, D, bf),
                             lambda e, i, j, tbl: (tbl[e], 0, j)),
            ],
            out_specs=pl.BlockSpec((1, bc, bf),
                                   lambda e, i, j, tbl: (e, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((E_local, C_run, F), x.dtype),
        interpret=interpret,
        name="paged_gmm",
    )(table, x, pool)
    return out[:, :C] if C_run != C else out


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def paged_expert_ffn(table_i, table_g, table_o, pool_i, pool_g, pool_o, x,
                     *, block_c: int = 128, block_f: int = 128,
                     interpret: bool = False):
    """Full SwiGLU expert FFN over paged weights:
    ``down( up(x) * silu(gate(x)) )`` with independent page tables for the
    three weight banks (they migrate independently during EP remap)."""
    h = paged_gmm(table_i, pool_i, x, block_c=block_c, block_f=block_f,
                  interpret=interpret)
    g = paged_gmm(table_g, pool_g, x, block_c=block_c, block_f=block_f,
                  interpret=interpret)
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    return paged_gmm(table_o, pool_o, h, block_c=block_c, block_f=block_f,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def quant_paged_gmm(table: jax.Array, pool: jax.Array, scales: jax.Array,
                    x: jax.Array, *, block_c: int = 128, block_f: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Int8 variant of ``paged_gmm``: ``pool`` is int8 ``[n_pages, D, F]``
    and ``scales`` the per-page f32 dequant scales ``[n_pages]`` (one scalar
    per page, ``kernels.quant.quantize_rows`` over ``(-2, -1)``).  The
    scales are gathered through the same page table as the weight pages,
    so remapped pages always compute with their own scale.  Output in
    ``x.dtype``; oracle: ``ref.quant_paged_gmm_ref``."""
    E_local, C, D = x.shape
    n_pages, D2, F = pool.shape
    assert D == D2, (D, D2)
    bc = min(block_c, C)
    if C % bc:
        C_pad = -(-C // bc) * bc
        x = jnp.pad(x, ((0, 0), (0, C_pad - C), (0, 0)))
    bf = _clamp_block_f(F, block_f)
    C_run = x.shape[1]
    # one scale per local expert, read from SMEM: a (1, 1) block of an
    # [n_pages, 1] array is not a Mosaic-legal block shape
    expert_scales = scales.astype(jnp.float32)[table]

    grid = (E_local, C_run // bc, F // bf)
    out = pl.pallas_call(
        _quant_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bc, D), lambda e, i, j, tbl: (e, i, 0)),
                pl.BlockSpec((1, D, bf),
                             lambda e, i, j, tbl: (tbl[e], 0, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, bc, bf),
                                   lambda e, i, j, tbl: (e, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((E_local, C_run, F), x.dtype),
        interpret=interpret,
        name="quant_paged_gmm",
    )(table, x, pool, expert_scales)
    return out[:, :C] if C_run != C else out


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def quant_paged_expert_ffn(table_i, table_g, table_o, pool_i, pool_g, pool_o,
                           scale_i, scale_g, scale_o, x,
                           *, block_c: int = 128, block_f: int = 128,
                           interpret: bool = False):
    """SwiGLU expert FFN over int8 paged weights with per-page f32 scales
    (one per bank — they migrate with their bank during EP remap)."""
    h = quant_paged_gmm(table_i, pool_i, scale_i, x, block_c=block_c,
                        block_f=block_f, interpret=interpret)
    g = quant_paged_gmm(table_g, pool_g, scale_g, x, block_c=block_c,
                        block_f=block_f, interpret=interpret)
    h = h * jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)
    return quant_paged_gmm(table_o, pool_o, scale_o, h, block_c=block_c,
                           block_f=block_f, interpret=interpret)
