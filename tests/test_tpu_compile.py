"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

Each serving kernel, and the engine's whole decode + chunk-prefill step on
one chip and on two, is compiled by the TPU compiler at qwen3-30b-a3b
widths; the compiler refuses what the chip would (block shapes, VMEM,
kernels it cannot partition).  The compiled steps are also held to keep the
KV pool in place: no slice, update-slice or copy of a pool leaf.  The v5e:2x2 topology is described inside a module fixture,
never at import, so every pytest-xdist worker collects the same tests.  The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.

The last test runs on the CPU and steers ``jax.default_backend`` itself:
off the CPU, ``kernels.ops`` must choose neither interpret mode nor the
jnp oracles.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen3-30b-a3b widths (arXiv:2505.09388); decode batch 16, 2048-token
# context in 16-token blocks, 256-token prefill chunks
B, H, KVH, HD, BS, MB, CHUNK = 16, 32, 4, 128, 16, 128, 256
NB = 2048
E, D, F, PAGES = 128, 2048, 768, 768


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, sh):
    """(function, argument shapes) of one serving kernel."""
    from repro.kernels import moe_gmm as mg
    from repro.kernels import paged_attention as pa

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    bf, i32, f32, i8 = jnp.bfloat16, jnp.int32, jnp.float32, jnp.int8
    pool = lambda dt: s((NB, BS, KVH, HD), dt)   # noqa: E731
    scales = s((NB, BS), f32)
    tables = [s((E,), i32)] * 3
    x = s((E, B, D), bf)
    return {
        "block_decode": (pa.block_paged_decode_attention,
                         [s((B, H, HD), bf), pool(bf), pool(bf),
                          s((B, MB), i32), s((B,), i32)]),
        "quant_block_decode": (pa.quant_block_paged_decode_attention,
                               [s((B, H, HD), bf), pool(i8), scales,
                                pool(i8), scales, s((B, MB), i32),
                                s((B,), i32)]),
        "mixed": (pa.mixed_block_paged_attention,
                  [s((1, CHUNK, H, HD), bf), pool(bf), pool(bf),
                   s((1, MB), i32), s((1,), i32), s((1,), i32)]),
        "quant_mixed": (pa.quant_mixed_block_paged_attention,
                        [s((1, CHUNK, H, HD), bf), pool(i8), scales,
                         pool(i8), scales, s((1, MB), i32), s((1,), i32),
                         s((1,), i32)]),
        "paged_expert_ffn": (mg.paged_expert_ffn,
                             tables + [s((PAGES, D, F), bf),
                                       s((PAGES, D, F), bf),
                                       s((PAGES, F, D), bf), x]),
        "quant_paged_expert_ffn": (mg.quant_paged_expert_ffn,
                                   tables + [s((PAGES, D, F), i8),
                                             s((PAGES, D, F), i8),
                                             s((PAGES, F, D), i8)]
                                   + [s((PAGES,), f32)] * 3 + [x]),
    }[name]


@pytest.mark.parametrize("name", [
    "block_decode", "quant_block_decode", "mixed", "quant_mixed",
    "paged_expert_ffn", "quant_paged_expert_ffn"])
def test_serving_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


# what the layer scan must not do to the KV pool: slice a layer out, write
# it back, or copy it (a Mosaic call cannot fuse a slice, so XLA would
# materialise one)
_POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _pool_moves(text, leaves):
    """HLO instructions of a pool leaf's shape (``leaves``: dims tuples)
    that are, or are fusions named after, a copy, dynamic-slice or
    dynamic-update-slice."""
    shapes = {",".join(map(str, d)) for d in leaves}
    found = []
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m is None or m.group(2) not in shapes:
            continue
        name, op = m.group(1), m.group(3)
        if op in _POOL_MOVES or (op == "fusion" and any(
                w in name for w in _POOL_MOVES)):
            found.append(f"{op} {name} [{m.group(2)}]")
    return found


def _pool_leaves(L, nb, parts):
    """A layer's pool leaf, the stacked leaf, and each per-partition share,
    at the test's block size and qwen3-30b-a3b's KV heads."""
    out = set()
    for n in {nb, nb // parts}:
        out |= {(n, BS, KVH, HD), (L, n, BS, KVH, HD), (1, n, BS, KVH, HD)}
    return out


def _serving_instance(topo, monkeypatch, dp, **hmm_kw):
    from repro.core.hmm import HMM
    from repro.core.imm import IMM
    from repro.core.topology import ElasticConfig
    from repro.kernels import ops
    from repro.launch.serve import model_config
    # the described chips are not the CPU backend this process runs on
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    cfg = model_config("qwen3-30b-a3b", 2)
    bpr = hmm_kw.pop("batch_per_replica", 4)
    hmm = HMM(cfg, 1, batch_per_replica=bpr, max_len=MB * BS,
              all_devices=list(topo.devices), kv_mode="paged",
              kv_block_size=BS, expert_mode="pooled",
              expert_pool_pages=cfg.num_layers * E // 2, staging="overlap",
              **hmm_kw)
    imm = IMM(cfg, hmm, batch_per_replica=bpr, max_len=MB * BS,
              prefill_buckets=(CHUNK,), prefill_chunk=CHUNK)
    inst = imm.preinitialize(ElasticConfig(dp=dp, tp=1,
                                           devices=tuple(range(dp))))
    assert set(inst.compiled) == {"decode", f"chunk_prefill_{CHUNK}"}
    return cfg, hmm, inst


def test_serving_step_compiles_on_two_v5e_chips(topo, monkeypatch):
    """The engine's decode and chunk-prefill executables on a dp=2 mesh of
    described chips: the paged-attention kernel runs once per KV partition
    (a Mosaic kernel cannot be partitioned automatically) and the pooled
    expert FFN inside the expert-parallel shard_map.  The stacked pool,
    sharded on its block axis, reaches the kernel whole: no layer or
    partition of it is sliced out or copied."""
    cfg, hmm, inst = _serving_instance(topo, monkeypatch, 2)
    leaves = _pool_leaves(cfg.num_layers, 2 * hmm.kv_blocks_per_replica, 2)
    for exe in inst.compiled.values():
        text = exe.as_text()
        kernels = set(re.findall(r"%(paged_attention|paged_gmm)[.\d]* = ",
                                 text))
        assert kernels == {"paged_attention", "paged_gmm"}
        assert "all-to-all" in text
        assert _pool_moves(text, leaves) == []


def test_serving_step_keeps_kv_pool_in_place_on_one_v5e_chip(topo,
                                                             monkeypatch):
    """One chip, the benchmark's serving shape (64 slots) with a pool large
    enough that one layer of it outweighs every other temporary: decode and
    chunk prefill write and read the stacked pool in place.  No pool-shaped
    slice, update-slice or copy, and the executables' temporaries stay
    below one layer's K and V pools (slicing even one layer out would not
    fit under that)."""
    nb = 16384
    cfg, hmm, inst = _serving_instance(topo, monkeypatch, 1,
                                       batch_per_replica=64,
                                       kv_blocks_per_replica=nb)
    leaves = _pool_leaves(cfg.num_layers, nb, 1)
    layer_bytes = 2 * nb * BS * KVH * HD * jnp.dtype(jnp.bfloat16).itemsize
    for name, exe in inst.compiled.items():
        assert _pool_moves(exe.as_text(), leaves) == [], name
        mem = exe.memory_analysis()
        if mem is not None:
            assert mem.temp_size_in_bytes < layer_bytes, (
                name, mem.temp_size_in_bytes, layer_bytes)


def test_pool_move_scan_finds_a_sliced_layer():
    """The HLO scan of the two tests above flags a layer sliced out of the
    stacked pool, its write-back and a copy, and passes the in-place
    scatter and the whole pool as a parameter."""
    text = """
  %p = bf16[6,64,16,4,128]{4,3,2,1,0} parameter(0)
  %dynamic_slice.1 = bf16[1,64,16,4,128]{4,3,2,1,0} dynamic-slice(%p, %i)
  %dynamic-slice_bitcast_fusion.4 = bf16[64,16,4,128]{3,2,1,0} fusion(%p), kind=kLoop
  %dynamic_update_slice.2 = bf16[6,64,16,4,128]{4,3,2,1,0} dynamic-update-slice(%p, %u, %i)
  %copy.3 = bf16[6,64,16,4,128]{4,3,2,1,0:T(4,128)(2,1)} copy(%p)
  ROOT %scatter.5 = bf16[6,64,16,4,128]{4,3,2,1,0} scatter(%p, %i, %u)
  %fusion.6 = bf16[6,64,16,4,128]{4,3,2,1,0} fusion(%p, %i, %u), kind=kCustom
"""
    found = _pool_moves(text, _pool_leaves(6, 64, 1))
    assert found == [
        "dynamic-slice dynamic_slice.1 [1,64,16,4,128]",
        "fusion dynamic-slice_bitcast_fusion.4 [64,16,4,128]",
        "dynamic-update-slice dynamic_update_slice.2 [6,64,16,4,128]",
        "copy copy.3 [6,64,16,4,128]"]


def test_ops_never_interprets_or_falls_back_off_cpu(monkeypatch):
    from repro.kernels import ops
    for env in ops.IMPL_ENV:
        monkeypatch.delenv(env, raising=False)
    assert ops.on_cpu()
    assert ops._interpret({})["interpret"] is True
    assert ops._use_ref({}, "REPRO_PAGED_IMPL") is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not ops.on_cpu()
    assert ops._interpret({})["interpret"] is False
    for env in ops.IMPL_ENV:
        assert ops._use_ref({}, env) is False
    # a traced serving call holds the compiled kernel, not the oracle
    sd = jax.ShapeDtypeStruct
    jaxpr = str(jax.make_jaxpr(ops.block_paged_decode_attention)(
        sd((2, 8, 128), jnp.bfloat16), sd((16, 16, 2, 128), jnp.bfloat16),
        sd((16, 16, 2, 128), jnp.bfloat16), sd((2, 4), jnp.int32),
        sd((2,), jnp.int32)))
    assert "pallas_call" in jaxpr and "interpret=False" in jaxpr
    assert "interpret=True" not in jaxpr
