"""What a plain reference is built from: float32 at ``highest`` precision.

A configuration's architecture file (``bench/arch/<arch>.py``) holds its
forward pass, ``hidden`` and ``head``, in straightforward ``jax.numpy``,
with no kernel, cache, batching by slot or capacity.  It imports nothing
of the program and reads the benchmark's own weights, one layer at a time,
so it fits beside nothing else.  This module is the arithmetic those
files share: the matmuls (``MATMUL``), RMSNorm, rotary embedding and
causal softmax attention.

``MATMUL["fp8"]`` / ``["int8"]`` compute a projection in that type
(activations with one scale per row, weights one per output column,
accumulated in float32): the two steps below the bfloat16 the
configurations serve, the controls of the check.  A router stays float32
(``mm``), as the program keeps it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

f32 = jnp.float32


def mm(x, w):
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
    def mm_below(x, w):
        return jnp.matmul(rnd(x, -1), rnd(w.astype(f32), -2),
                          precision="highest")
    return mm_below


MATMUL = {"f32": mm, "fp8": _mm_below(_fp8), "int8": _mm_below(_int8)}


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(f32)


def rope(x, pos, base):
    """x [S, heads, d]: rotate interleaved pairs (x[2i], x[2i+1])."""
    d = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=f32) / d))
    ang = pos[:, None].astype(f32) * inv                      # [S, d/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def causal_softmax_mix(scores, v):
    """scores [H, S, S] (query, key), v [S, H, dv] -> [S, H, dv]."""
    S = scores.shape[-1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqt,thd->qhd", p, v, precision="highest")
