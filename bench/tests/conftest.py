"""CPU fixtures: cells at a size a test run holds.

The configurations keep each architecture's structure (GQA + 128-way
routing becomes 8-way, MLA keeps its decoupled rotary key and the dense
first layer) at widths the CPU runs in seconds.  Traffic and serving
shapes shrink with them.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import pytest  # noqa: E402

from harness import spec  # noqa: E402

GQA = {
    "registry": "qwen3-30b-a3b", "arch": "softmax_moe", "hidden_size": 128,
    "head_dim": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "rope_scaling": None, "dtype": "bfloat16",
    "serving": {"batch_per_replica": 4, "max_len": 256, "prefill_chunk": 32,
                "prefill_buckets": [32], "kv_block": 16,
                "kv_mode": "paged"}}

MLA = {
    "registry": "deepseek-v2-lite-16b", "arch": "softmax_moe",
    "hidden_size": 128, "first_k_dense_replace": 1, "intermediate_size": 256,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "moe_intermediate_size": 64,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "num_hidden_layers": 3, "vocab_size": 512,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
    "dtype": "bfloat16",
    "serving": {"batch_per_replica": 4, "max_len": 256, "prefill_chunk": 0,
                "prefill_buckets": [32, 64, 96], "kv_block": 16,
                "kv_mode": "dense"}}

MIX = {"prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                  "min": 8, "max": 96},
       "output": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                  "min": 2, "max": 48},
       "warmup_s": 1}

def small_cell(conf, limit=0.05, rate=4.0):
    """A cell with the metrics of ``qwen3_30b_a3b.chat`` and these sizes."""
    real = spec.load_cell("qwen3_30b_a3b.chat")
    return spec.Cell(name="small.chat", chips=1, config=conf,
                     arch=spec.arch(conf), traffic=MIX,
                     params={"rate_rps": rate, "sample_requests": 4,
                             "limits": {"mean_gap": limit}},
                     per_layer=real.per_layer, end_to_end=real.end_to_end,
                     units=real.units)


@pytest.fixture(params=["gqa", "mla"])
def conf(request):
    return {"gqa": GQA, "mla": MLA}[request.param]
