"""Training substrate + serving metrics + cost model + HLO analyzer tests."""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config


def test_training_loss_decreases(tmp_path):
    from repro.training.train_loop import train
    cfg = get_config("qwen1.5-0.5b-smoke")
    out = train(cfg, steps=25, batch=8, seq_len=64, log_every=5)
    assert out["history"][-1][1] < out["history"][0][1]


def test_checkpoint_roundtrip(tmp_path):
    from repro.models.model import init_params
    from repro.training import checkpoint
    cfg = get_config("yi-6b-smoke")
    params = init_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, params)
    restored = checkpoint.restore(path, jax.eval_shape(lambda: params))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, restored)


def test_metrics_ttft_tpot_slo():
    from repro.serving.metrics import SLO, meets_slo, slo_attainment
    from repro.serving.workload import Request
    r = Request(0, 10.0, 100, 11)
    r.first_token_s = 10.5
    r.finish_s = 15.5
    assert abs(r.ttft - 0.5) < 1e-9
    assert abs(r.tpot - 0.5) < 1e-9
    slo = SLO(ttft_s=1.0, tpot_s=1.0)
    assert meets_slo(r, slo)
    assert slo_attainment([r], slo) == 1.0
    assert meets_slo(r, SLO(ttft_s=0.1, tpot_s=1.0)) is False


def test_workload_rate_profiles():
    from repro.serving.workload import burst, make_workload, step_up
    reqs = make_workload(duration_s=30.0, rps_fn=step_up(1.0, 5.0, 15.0),
                         seed=0)
    early = sum(1 for r in reqs if r.arrival_s < 15)
    late = sum(1 for r in reqs if r.arrival_s >= 15)
    assert late > 2 * early


def test_load_estimator_decisions():
    from repro.core.coordinator import LoadEstimator, ScalingPolicy
    from repro.serving.metrics import SLO
    from repro.serving.workload import Request
    pol = ScalingPolicy(slo=SLO(1.0, 1.0), window=8, cooldown_s=0.0)
    est = LoadEstimator(pol)
    for i in range(8):
        r = Request(i, 0.0, 10, 5)
        r.first_token_s = 5.0   # ttft 5s -> violation
        r.finish_s = 6.0
        est.record(r)
    assert est.decide(100.0, queue_depth=0, utilization=0.9) == "up"
    for i in range(8):
        r = Request(i, 0.0, 10, 5)
        r.first_token_s = 0.1
        r.finish_s = 0.5
        est.record(r)
    assert est.decide(200.0, queue_depth=0, utilization=0.1) == "down"


def test_cost_model_reproduces_table1_ordering():
    """Ablation ordering (Table 1): full < -IPCAlloc < -HCCL < -PreInit <
    -ZeroCopy; downtime only without zero-copy."""
    from repro.core.costmodel import plan_cost
    from repro.core.scaling_plan import plan_elastic
    from repro.core.topology import ElasticConfig, kv_cache_bytes, model_tensors
    mcfg = get_config("deepseek-v2-lite-16b")
    tensors = model_tensors(mcfg, tp=2,
                            kv_bytes_per_replica=kv_cache_bytes(mcfg, 8, 4096))
    old = ElasticConfig(dp=3, tp=2, devices=tuple(range(6)))
    new = ElasticConfig(dp=4, tp=2, devices=tuple(range(8)))
    plan = plan_elastic(tensors, old, new)
    full = plan_cost(plan)
    no_ipc = plan_cost(plan, ipc_safe_alloc=False)
    no_hccl = plan_cost(plan, ipc_safe_alloc=False, hccl=False)
    no_pre = plan_cost(plan, ipc_safe_alloc=False, hccl=False, preinit=False)
    no_zc = plan_cost(plan, ipc_safe_alloc=False, hccl=False, preinit=False,
                      zero_copy=False)
    ts = [full.scale_time_s, no_ipc.scale_time_s, no_hccl.scale_time_s,
          no_pre.scale_time_s, no_zc.scale_time_s]
    assert ts == sorted(ts), ts
    assert full.downtime_s == 0 and no_pre.downtime_s == 0
    assert no_zc.downtime_s > 0
    assert no_ipc.peak_mem_gb > full.peak_mem_gb


def test_hlo_analyzer_counts_loops_and_collectives():
    """Known program: scan of n matmuls + psum -> analyzer must count
    n * 2*M*N*K flops and the all-reduce bytes."""
    from repro.analysis.hlo_costs import analyze
    n, m = 5, 128

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y.sum()

    x = jnp.ones((m, m), jnp.float32)
    w = jnp.ones((m, m), jnp.float32)
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    costs = analyze(hlo)
    want = n * 2 * m * m * m
    assert 0.5 * want <= costs.flops <= 1.5 * want, (costs.flops, want)


def test_roofline_terms_and_bottleneck():
    from repro.analysis.roofline import Roofline
    r = Roofline(flops=1e15, hbm_bytes=1e12, coll_bytes={"all-reduce": 1e11},
                 chips=256, device_kind="TPU v5 lite", model_flops=5e14)
    assert abs(r.t_compute - 1e15 / (256 * 197e12)) < 1e-12
    assert r.bottleneck in ("compute", "memory", "collective")
    assert 0 < r.useful_flops_ratio < 1
    # a chip with no published peaks is an error, not a default
    with pytest.raises(KeyError):
        dataclasses.replace(r, device_kind="cpu").t_compute


def test_optimized_sharding_rules():
    """§Perf sharding rules: head-aligned KV replication and flash-decoding
    seq sharding of KV / MLA-latent caches."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.sharding import cache_specs, param_specs
    from repro.models.model import init_cache, init_params

    devs = np.array(jax.devices() * 1)  # 1 device: shapes only matter
    # fake a (data=1, model=1) mesh: divisibility rules still evaluated vs 1
    # -> use eval_shape trees with a 16x16-shaped Mesh of repeated devices?
    # jax requires unique devices; test the rule function on shapes directly
    from repro.distributed.sharding import _spec_for_path

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    m = FakeMesh()
    # kv proj 2 heads x 128 = 256 divisible by 16, but head-misaligned:
    s_naive = _spec_for_path("blocks/attn/k/w", (28, 4096, 256), m, 1, None)
    s_aligned = _spec_for_path("blocks/attn/k/w", (28, 4096, 256), m, 1, 2)
    assert s_naive == P(None, None, "model")
    assert s_aligned == P(None, None, None)
    # 32 kv heads stay sharded either way
    s32 = _spec_for_path("blocks/attn/k/w", (32, 2560, 2560), m, 1, 32)
    assert s32 == P(None, None, "model")

    cfg = get_config("chatglm3-6b")
    cache = jax.eval_shape(lambda: init_cache(cfg, 128, 1024))
    specs = cache_specs(cfg, cache, m, kv_seq_shard=True)
    assert specs["k"] == P(None, ("data",), "model", None, None)
    cfg2 = get_config("deepseek-v2-lite-16b")
    cache2 = jax.eval_shape(lambda: init_cache(cfg2, 128, 1024))
    specs2 = cache_specs(cfg2, cache2, m, kv_seq_shard=True)
    assert specs2["c"] == P(None, ("data",), "model", None)
