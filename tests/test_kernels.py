"""Per-kernel allclose vs pure-jnp oracles, sweeping shapes and dtypes
(interpret=True on CPU) — deliverable (c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F,P", [
    (2, 128, 64, 128, 4),
    (4, 256, 128, 256, 9),
    (1, 128, 32, 128, 2),
])
def test_paged_gmm(E, C, D, F, P, dtype):
    table = jnp.asarray(RNG.permutation(P)[:E].astype(np.int32))
    pool = jnp.asarray(RNG.standard_normal((P, D, F)), dtype)
    x = jnp.asarray(RNG.standard_normal((E, C, D)), dtype)
    got = ops.paged_gmm(table, pool, x)
    want = ref.paged_gmm_ref(table, pool, x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_paged_expert_ffn():
    E, C, D, F, P = 3, 128, 64, 128, 6
    ti, tg, to = (jnp.asarray(RNG.permutation(P)[:E].astype(np.int32))
                  for _ in range(3))
    pi = jnp.asarray(RNG.standard_normal((P, D, F)), jnp.float32)
    pg = jnp.asarray(RNG.standard_normal((P, D, F)), jnp.float32)
    po = jnp.asarray(RNG.standard_normal((P, F, D)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((E, C, D)), jnp.float32)
    # impl='kernel' forces the Pallas path (ops defaults to the ref oracle
    # on CPU per REPRO_POOLED_IMPL, which would compare ref to itself here)
    got = ops.paged_expert_ffn(ti, tg, to, pi, pg, po, x, impl="kernel")
    want = ref.paged_expert_ffn_ref(ti, tg, to, pi, pg, po, x)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    # the ops-level default (CPU -> ref fallback) must agree too
    got_auto = ops.paged_expert_ffn(ti, tg, to, pi, pg, po, x)
    np.testing.assert_allclose(got_auto, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("E,C,D,F,bc,bf", [
    (2, 200, 64, 128, 128, 128),    # C % block_c != 0 -> zero-pad C
    (3, 128, 32, 192, 128, 128),    # F % 128-block -> clamp to full dim
    (2, 100, 64, 144, 64, 128),     # both ragged at once
    (1, 128, 32, 384, 128, 256),    # F clamps 256 -> aligned divisor 128
    (1, 128, 32, 130, 128, 128),    # F prime-ish -> full-dim lane tile
])
def test_paged_gmm_unaligned_blocks(E, C, D, F, bc, bf):
    """Pad-or-clamp: token counts not divisible by block_c are zero-padded
    (zero rows produce zero outputs, sliced off); hidden dims not divisible
    by block_f clamp the block to a 128-aligned divisor or the full dim
    (never an unaligned lane tile — Mosaic constraint; padding F would copy
    every pool page).  Results must match the oracle exactly either way."""
    P = E + 2
    table = jnp.asarray(RNG.permutation(P)[:E].astype(np.int32))
    pool = jnp.asarray(RNG.standard_normal((P, D, F)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((E, C, D)), jnp.float32)
    got = ops.paged_gmm(table, pool, x, block_c=bc, block_f=bf)
    want = ref.paged_gmm_ref(table, pool, x)
    assert got.shape == want.shape == (E, C, F)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_paged_gmm_bf16_vs_f32_oracle():
    """bf16 kernel against the f32 oracle (not the bf16 oracle): the paged
    indirection must not add error beyond bf16 rounding of inputs."""
    E, C, D, F, P = 2, 128, 64, 128, 5
    table = jnp.asarray(RNG.permutation(P)[:E].astype(np.int32))
    pool32 = jnp.asarray(RNG.standard_normal((P, D, F)), jnp.float32)
    x32 = jnp.asarray(RNG.standard_normal((E, C, D)), jnp.float32)
    got = ops.paged_gmm(table, pool32.astype(jnp.bfloat16),
                        x32.astype(jnp.bfloat16))
    want = ref.paged_gmm_ref(table, pool32, x32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-1)


def test_paged_gmm_aliased_table_entries():
    """Table entries pointing at the SAME page (post-CoW-style sharing):
    every aliased expert must read identical weights — each grid step only
    dereferences pool[table[e]], so aliasing is free."""
    E, C, D, F, P = 4, 128, 32, 128, 6
    table = jnp.asarray(np.array([3, 3, 5, 3], np.int32))
    pool = jnp.asarray(RNG.standard_normal((P, D, F)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((E, C, D)), jnp.float32)
    got = ops.paged_gmm(table, pool, x)
    want = ref.paged_gmm_ref(table, pool, x)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
    # experts 0, 1 and 3 share page 3: same inputs -> identical outputs
    same_x = x.at[1].set(x[0]).at[3].set(x[0])
    out = ops.paged_gmm(table, pool, same_x)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[3]))


def test_paged_gmm_remap_invariance():
    """Permuting pages + updating the table must not change results — the
    vpage-remap guarantee at kernel level."""
    E, C, D, F, P = 4, 128, 32, 128, 8
    table = jnp.arange(E, dtype=jnp.int32)
    pool = jnp.asarray(RNG.standard_normal((P, D, F)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((E, C, D)), jnp.float32)
    base = ops.paged_gmm(table, pool, x)
    perm = RNG.permutation(P)
    pool2 = pool[jnp.asarray(np.argsort(perm))]          # pages physically moved
    table2 = jnp.asarray(perm[np.asarray(table)], np.int32)
    got = ops.paged_gmm(table2, pool2, x)
    np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KVH,hd,bq,bk", [
    (2, 256, 4, 2, 64, 128, 128),
    (1, 512, 8, 8, 128, 128, 256),
    (2, 128, 4, 1, 80, 64, 64),
])
def test_flash_attention(B, S, H, KVH, hd, bq, bk, dtype):
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), dtype)
    got = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_non_causal():
    B, S, H, hd = 1, 256, 2, 64
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,hd,S", [
    (3, 8, 2, 64, 256),
    (2, 4, 4, 128, 512),
    (1, 16, 2, 80, 128),
])
def test_paged_decode_attention(B, H, KVH, hd, S, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), dtype)
    kc = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), dtype)
    vc = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), dtype)
    lengths = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    got = ops.paged_decode_attention(q, kc, vc, lengths)
    want = ref.paged_decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def _pool_cases(*cases):
    """Kernel shape cases; a trailing ``(L, layer)`` reads pool layer
    ``layer`` of a stacked ``[L, NB, ...]`` pool, ``None`` a single-layer
    ``[NB, ...]`` pool (the case id is then the shape alone)."""
    return [pytest.param(*c, id="-".join(map(str, c[:-1])) + (
        "" if c[-1] is None else "-L{}-layer{}".format(*c[-1])))
        for c in cases]


def _pools(NB, bs, KVH, hd, dtype, stack):
    """(k_pool, v_pool, k_at, v_at, layer): the pools the kernel reads and
    the single layer the oracle reads; the other layers are different
    random rows, so a kernel reading the wrong layer cannot pass."""
    shape = (NB, bs, KVH, hd) if stack is None else (stack[0], NB, bs, KVH,
                                                      hd)
    kp = jnp.asarray(RNG.standard_normal(shape), dtype)
    vp = jnp.asarray(RNG.standard_normal(shape), dtype)
    if stack is None:
        return kp, vp, kp, vp, None
    layer = stack[1]
    return kp, vp, kp[layer], vp[layer], jnp.int32(layer)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,hd,NB,bs,MB,stack", _pool_cases(
    (3, 8, 2, 64, 16, 128, 4, None),
    (2, 4, 4, 128, 8, 256, 2, None),
    (1, 16, 2, 80, 12, 64, 6, None),
    (3, 8, 2, 64, 16, 128, 4, (3, 2)),
    (2, 32, 4, 128, 24, 16, 8, (6, 1)),
))
def test_block_paged_decode_attention(B, H, KVH, hd, NB, bs, MB, stack,
                                      dtype):
    """Pallas block-table kernel vs the jnp gather oracle: per-sequence
    block tables index a shared [NB, bs, KVH, hd] pool — or one layer of a
    stacked [L, NB, bs, KVH, hd] pool, which must equal the oracle on that
    layer's pool alone."""
    from repro.kernels.paged_attention import block_paged_decode_attention
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), dtype)
    kp, vp, k_at, v_at, layer = _pools(NB, bs, KVH, hd, dtype, stack)
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB)
                     .astype(np.int32))
    lengths = jnp.asarray(RNG.integers(1, MB * bs + 1, B), jnp.int32)
    want = ref.block_paged_decode_attention_ref(q, k_at, v_at, bt, lengths)
    got = block_paged_decode_attention(q, kp, vp, bt, lengths, layer,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))
    # ops export: ref fallback on CPU must agree too
    got_ops = ops.block_paged_decode_attention(q, kp, vp, bt, lengths, layer)
    np.testing.assert_allclose(np.asarray(got_ops, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KVH,hd,NB,bs,MB,Sq,stack", _pool_cases(
    (2, 4, 2, 64, 12, 64, 4, 32, None),
    (1, 8, 4, 128, 8, 128, 2, 48, None),
    (3, 4, 1, 80, 20, 32, 6, 16, None),
    (2, 4, 2, 64, 12, 64, 4, 32, (2, 1)),
    (1, 32, 4, 128, 24, 16, 8, 64, (6, 5)),
))
def test_mixed_block_paged_attention(B, H, KVH, hd, NB, bs, MB, Sq, stack,
                                     dtype):
    """Mixed chunked-prefill/decode kernel vs the jnp gather oracle, with
    per-sequence chunk lengths deliberately unaligned to both the compiled
    ``Sq`` bucket and the block size, over a single-layer pool or one layer
    of a stacked pool.  Only valid chunk rows are compared — padding rows
    degrade to full-context decode masking by design."""
    from repro.kernels.paged_attention import mixed_block_paged_attention
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, hd)), dtype)
    kp, vp, k_at, v_at, layer = _pools(NB, bs, KVH, hd, dtype, stack)
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB)
                     .astype(np.int32))
    q_lens = RNG.integers(1, Sq + 1, B)
    ctx = np.array([RNG.integers(ql, MB * bs + 1) for ql in q_lens])
    q_lens, ctx = jnp.asarray(q_lens, jnp.int32), jnp.asarray(ctx, jnp.int32)
    want = ref.mixed_block_paged_attention_ref(q, k_at, v_at, bt, ctx, q_lens)
    got = mixed_block_paged_attention(q, kp, vp, bt, ctx, q_lens, layer,
                                      interpret=True)
    got_ops = ops.mixed_block_paged_attention(q, kp, vp, bt, ctx, q_lens,
                                              layer)
    for b in range(B):
        n = int(q_lens[b])
        np.testing.assert_allclose(np.asarray(got[b, :n], np.float32),
                                   np.asarray(want[b, :n], np.float32),
                                   **tol(dtype))
        # ops export: ref fallback on CPU (REPRO_PAGED_IMPL) must agree too
        np.testing.assert_allclose(np.asarray(got_ops[b, :n], np.float32),
                                   np.asarray(want[b, :n], np.float32),
                                   **tol(dtype))


@pytest.mark.parametrize("kind", ["decode", "mixed"])
@pytest.mark.parametrize("L,layer", [(1, 0), (4, 3)])
def test_quant_paged_attention_reads_stacked_layer(kind, L, layer):
    """The int8 kernels over stacked int8 pools [L, NB, bs, KVH, hd] and
    scale pools [L, NB, bs], read at ``layer``, equal the dequant oracle on
    that layer's pools alone: entries and scales come from the same layer
    and block."""
    from repro.kernels.quant import quantize_rows
    B, H, KVH, hd, NB, bs, MB, Sq = 2, 8, 4, 64, 12, 16, 4, 8
    kq, ks = quantize_rows(jnp.asarray(
        RNG.standard_normal((L, NB, bs, KVH, hd)), jnp.float32), (-2, -1))
    vq, vs = quantize_rows(jnp.asarray(
        RNG.standard_normal((L, NB, bs, KVH, hd)), jnp.float32), (-2, -1))
    at = [a[layer] for a in (kq, ks, vq, vs)]
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB), jnp.int32)
    ly = jnp.int32(layer)
    if kind == "decode":
        q = jnp.asarray(RNG.standard_normal((B, H, hd)), jnp.float32)
        lengths = jnp.asarray([64, 23], jnp.int32)
        got = ops.quant_block_paged_decode_attention(
            q, kq, ks, vq, vs, bt, lengths, ly, impl="kernel")
        want = ref.quant_block_paged_decode_attention_ref(q, *at, bt, lengths)
        ref_stacked = ref.quant_block_paged_decode_attention_ref(
            q, kq, ks, vq, vs, bt, lengths, ly)
    else:
        q = jnp.asarray(RNG.standard_normal((B, Sq, H, hd)), jnp.float32)
        ctx, qlen = (jnp.asarray([40, 9], jnp.int32),
                     jnp.asarray([8, 1], jnp.int32))
        got = ops.quant_mixed_block_paged_attention(
            q, kq, ks, vq, vs, bt, ctx, qlen, ly, impl="kernel")
        want = ref.quant_mixed_block_paged_attention_ref(q, *at, bt, ctx,
                                                         qlen)
        ref_stacked = ref.quant_mixed_block_paged_attention_ref(
            q, kq, ks, vq, vs, bt, ctx, qlen, ly)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(ref_stacked), np.asarray(want))


def test_mixed_sentinel_block_rows_are_inert():
    """Block-table entries past a sequence's context may hold the NB
    sentinel (padding / CoW-dropped rows).  They are clamped in-bounds and
    position-masked, so swapping them for arbitrary live rows must not
    change a single output bit."""
    from repro.kernels.paged_attention import mixed_block_paged_attention
    B, H, KVH, hd, NB, bs, MB, Sq = 2, 4, 2, 64, 10, 32, 5, 16
    q = jnp.asarray(RNG.standard_normal((B, Sq, H, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((NB, bs, KVH, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((NB, bs, KVH, hd)), jnp.float32)
    ctx = jnp.asarray([40, 70], jnp.int32)              # 2 and 3 live blocks
    q_lens = jnp.asarray([7, 16], jnp.int32)
    base_bt = RNG.permutation(NB)[:B * MB].reshape(B, MB).astype(np.int32)
    sent = base_bt.copy()
    junk = base_bt.copy()
    for b in range(B):
        live = (int(ctx[b]) + bs - 1) // bs
        sent[b, live:] = NB                              # sentinel rows
        junk[b, live:] = RNG.integers(0, NB, MB - live)  # arbitrary rows
    outs = [mixed_block_paged_attention(q, kp, vp, jnp.asarray(t), ctx,
                                        q_lens, interpret=True)
            for t in (sent, junk, base_bt)]
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[2]))
    want = ref.mixed_block_paged_attention_ref(q, kp, vp, jnp.asarray(sent),
                                               ctx, q_lens)
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_mixed_qlen1_is_exactly_paged_decode():
    """``q_lens == 1`` collapses the mixed mask to plain paged decode — the
    property that lets one kernel serve interleaved prefill+decode ticks."""
    from repro.kernels.paged_attention import mixed_block_paged_attention
    B, H, KVH, hd, NB, bs, MB = 3, 8, 2, 64, 12, 64, 4
    q = jnp.asarray(RNG.standard_normal((B, 1, H, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((NB, bs, KVH, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((NB, bs, KVH, hd)), jnp.float32)
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB)
                     .astype(np.int32))
    ctx = jnp.asarray(RNG.integers(1, MB * bs + 1, B), jnp.int32)
    ones = jnp.ones((B,), jnp.int32)
    got = mixed_block_paged_attention(q, kp, vp, bt, ctx, ones,
                                      interpret=True)
    want = ref.block_paged_decode_attention_ref(q[:, 0], kp, vp, bt, ctx)
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_mixed_bf16_vs_f32_oracle():
    """bf16 mixed kernel against the f32 oracle: the paged indirection and
    online softmax must not add error beyond bf16 input rounding."""
    from repro.kernels.paged_attention import mixed_block_paged_attention
    B, H, KVH, hd, NB, bs, MB, Sq = 2, 4, 2, 64, 8, 64, 3, 24
    q32 = jnp.asarray(RNG.standard_normal((B, Sq, H, hd)), jnp.float32)
    kp32 = jnp.asarray(RNG.standard_normal((NB, bs, KVH, hd)), jnp.float32)
    vp32 = jnp.asarray(RNG.standard_normal((NB, bs, KVH, hd)), jnp.float32)
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB)
                     .astype(np.int32))
    ctx = jnp.asarray([100, 192], jnp.int32)
    q_lens = jnp.asarray([24, 13], jnp.int32)
    got = mixed_block_paged_attention(
        q32.astype(jnp.bfloat16), kp32.astype(jnp.bfloat16),
        vp32.astype(jnp.bfloat16), bt, ctx, q_lens, interpret=True)
    want = ref.mixed_block_paged_attention_ref(q32, kp32, vp32, bt, ctx,
                                               q_lens)
    for b in range(B):
        n = int(q_lens[b])
        np.testing.assert_allclose(np.asarray(got[b, :n], np.float32),
                                   np.asarray(want[b, :n], np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_block_paged_decode_remap_invariance():
    """Permuting pool rows + rewriting the tables must not change results —
    the zero-copy-remap guarantee at kernel level (what makes the HMM's
    commit-time pool growth safe for live sequences).  It holds for the
    stacked pool the serving step reads by layer: the rows of every layer
    move together along the block axis (axis 1)."""
    from repro.kernels.paged_attention import block_paged_decode_attention
    B, H, KVH, hd, NB, bs, MB, L = 2, 4, 2, 64, 12, 128, 3, 3
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), jnp.float32)
    kst = jnp.asarray(RNG.standard_normal((L, NB, bs, KVH, hd)), jnp.float32)
    vst = jnp.asarray(RNG.standard_normal((L, NB, bs, KVH, hd)), jnp.float32)
    kp, vp = kst[1], vst[1]
    bt = jnp.asarray(RNG.permutation(NB)[:B * MB].reshape(B, MB)
                     .astype(np.int32))
    lengths = jnp.asarray([200, 350], jnp.int32)
    base = block_paged_decode_attention(q, kp, vp, bt, lengths,
                                        interpret=True)
    perm = RNG.permutation(NB)
    inv = np.argsort(perm)
    kp2, vp2 = kp[jnp.asarray(inv)], vp[jnp.asarray(inv)]  # rows moved
    bt2 = jnp.asarray(perm[np.asarray(bt)].astype(np.int32))
    got = block_paged_decode_attention(q, kp2, vp2, bt2, lengths,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-6, atol=1e-6)
    for layer in range(L):
        stacked = block_paged_decode_attention(
            q, kst, vst, bt, lengths, jnp.int32(layer), interpret=True)
        moved = block_paged_decode_attention(
            q, kst[:, jnp.asarray(inv)], vst[:, jnp.asarray(inv)], bt2,
            lengths, jnp.int32(layer), interpret=True)
        np.testing.assert_array_equal(np.asarray(moved), np.asarray(stacked))
        if layer == 1:
            np.testing.assert_allclose(np.asarray(stacked), np.asarray(base),
                                       rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 64, 64),
    (2, 64, 8, 16, 8, 16),
])
def test_ssd_scan(B, S, H, P, N, chunk):
    x = jnp.asarray(RNG.standard_normal((B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((B, S, H)) * 0.5 + 0.01, jnp.float32)
    A = -jnp.asarray(RNG.random(H) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    y1, s1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


def test_ssd_scan_matches_model_ssd():
    """Kernel agrees with the model's chunked SSD (used in mamba2_forward)."""
    from repro.models.mamba2 import _ssd_chunked
    B, S, H, P, N = 1, 128, 2, 16, 8
    x = jnp.asarray(RNG.standard_normal((B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.random((B, S, H)) * 0.5 + 0.01, jnp.float32)
    A = -jnp.asarray(RNG.random(H) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    y1, s1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    y2, s2 = _ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,r,dr,S", [
    (2, 4, 128, 16, 256),
    (1, 16, 512, 64, 512),
    (3, 8, 256, 32, 128),
])
def test_mla_decode_attention(B, H, r, dr, S, dtype):
    qe = jnp.asarray(RNG.standard_normal((B, H, r)), dtype)
    qr = jnp.asarray(RNG.standard_normal((B, H, dr)), dtype)
    cc = jnp.asarray(RNG.standard_normal((B, S, r)), dtype)
    kr = jnp.asarray(RNG.standard_normal((B, S, dr)), dtype)
    lengths = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    got = ops.mla_decode_attention(qe, qr, cc, kr, lengths)
    want = ref.mla_decode_attention_ref(qe, qr, cc, kr, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,S,KVH,hd,block", [
    (3, 256, 2, 64, 128),
    (2, 512, 4, 128, 256),
    (1, 128, 1, 80, 64),
])
def test_kv_cache_write_inplace(B, S, KVH, hd, block):
    cache = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), jnp.float32)
    new = jnp.asarray(RNG.standard_normal((B, KVH, hd)), jnp.float32)
    pos = jnp.asarray(RNG.integers(0, S, B), jnp.int32)
    want = ref.kv_cache_write_ref(cache, new, pos)
    got = ops.kv_cache_write(cache, new, pos, block_s=block)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_attention_runs_per_kv_partition():
    """On a mesh whose 'dp' axis shards the pool, the Mosaic kernel runs
    once per KV partition (``layers.paged_attend``; a Mosaic kernel cannot
    be partitioned automatically).  The pools are stacked [L, NB, ...],
    sharded on the block axis (axis 1), and read at a replicated layer
    index.  A decode batch split across replicas, and one prefill chunk
    answered by the replica that owns its blocks, both equal the oracle
    over that layer's whole pool.  A row with a live block outside its home
    partition comes out NaN, never a wrong block's answer."""
    from helpers import run_with_devices
    run_with_devices("""
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.distributed.sharding import ParallelCtx
from repro.kernels import ops, ref
from repro.models.layers import paged_attend
n, nbp, bs, KVH, hd, H, bpr, MB, Sq, L = 4, 6, 8, 2, 64, 4, 2, 3, 8, 3
mesh = Mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("dp", "tp"))
ctx_ = ParallelCtx(mesh=mesh, ep_axes=("dp", "tp"), tp_axis="tp",
                   dp_axes=("dp",), moe_tp=False)
rng = np.random.default_rng(0)
NB = n * nbp
pool = lambda: jnp.asarray(rng.standard_normal((L, NB, bs, KVH, hd)),
                           jnp.float32)
kst, vst = pool(), pool()
layer = jnp.int32(2)
kp, vp = kst[2], vst[2]
put = lambda x: jax.device_put(x, NamedSharding(mesh, P(None, "dp")))
decode = jax.jit(lambda q, k, v, *a: paged_attend(
    ctx_, functools.partial(ops.block_paged_decode_attention, impl="kernel"),
    q, (k, v), layer, *a))
chunk = jax.jit(lambda q, k, v, *a: paged_attend(
    ctx_, functools.partial(ops.mixed_block_paged_attention, impl="kernel"),
    q, (k, v), layer, *a))
lens = rng.integers(1, MB * bs + 1, n * bpr).astype(np.int32)
bt = np.full((n * bpr, MB), NB, np.int32)          # NB = padding sentinel
for b in range(n * bpr):                    # slot b -> partition b // bpr
    live = -(-int(lens[b]) // bs)
    bt[b, :live] = rng.permutation(nbp)[:live] + (b // bpr) * nbp
q = jnp.asarray(rng.standard_normal((n * bpr, H, hd)), jnp.float32)
got = decode(q, put(kst), put(vst), bt, lens)
want = ref.block_paged_decode_attention_ref(q, kp, vp, bt, lens)
np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
qc = jnp.asarray(rng.standard_normal((1, Sq, H, hd)), jnp.float32)
ctx, ql = np.array([20], np.int32), np.array([Sq], np.int32)
tc = np.full((1, MB), NB, np.int32)
tc[0] = rng.permutation(nbp)[:MB] + 2 * nbp          # owned by replica 2
got = chunk(qc, put(kst), put(vst), tc, ctx, ql)
want = ref.mixed_block_paged_attention_ref(qc, kp, vp, tc, ctx, ql)
np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)
# a live block of slot 0 (replica 0) moved to replica 1's partition, and a
# chunk whose second block lies outside the partition of its first
bad = bt.copy()
bad[0, 0] = nbp
tbad = tc.copy()
tbad[0, 1] = 0
got = np.asarray(decode(q, put(kst), put(vst), bad, lens))
assert np.isnan(got[0]).all() and np.isfinite(got[1:]).all()
assert np.isnan(np.asarray(chunk(qc, put(kst), put(vst), tbad, ctx,
                                 ql))).all()
print("PARTITION-OK")
""", ndev=4)
