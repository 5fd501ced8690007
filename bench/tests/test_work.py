"""The FLOP and byte functions against hand counts at both
configurations' widths, and the rule that keeps a roofline share under
100%: only routed rows and touched expert pages count."""
import json
from pathlib import Path

import pytest

from harness import serve
from harness.spec import arch, plugin

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
QWEN = json.loads((CONFIGS / "qwen3_30b_a3b.json").read_text())
# DeepSeek-V2-Lite's published widths (huggingface.co/deepseek-ai/
# DeepSeek-V2-Lite config.json), 8 of its 27 layers
DSV2 = {"hidden_size": 2048, "moe_intermediate_size": 1408,
        "n_routed_experts": 64,
        "n_shared_experts": 2, "num_experts_per_tok": 6,
        "first_k_dense_replace": 1, "num_hidden_layers": 8}
gmm = plugin("work", "paged_gmm")
attn = plugin("work", "paged_attention")
step = plugin("work", "model_step")


def test_paged_gmm_decode_hand_count_qwen3():
    # 64 decoded tokens, top-8 of 128 experts, D 2048, F 768
    flops, nbytes = gmm.call(QWEN, 64)
    assert flops == 6 * 2048 * 768 * 64 * 8
    touched = 128 * (1 - (1 - 8 / 128) ** 64)
    assert touched == pytest.approx(125.94, abs=0.01)
    page = 3 * 2048 * 768 * 2
    assert nbytes == pytest.approx(touched * page + 2 * 64 * 8 * 2048 * 2)


def test_paged_gmm_chunk_hand_count_deepseek():
    # a 256-token chunk reaches every one of 64 experts (top-6)
    flops, nbytes = gmm.call(DSV2, 256)
    assert flops == 6 * 2048 * 1408 * 256 * 6
    assert nbytes == pytest.approx(64 * 3 * 2048 * 1408 * 2
                                   + 2 * 256 * 6 * 2048 * 2, rel=1e-9)


@pytest.mark.parametrize("conf", [QWEN, DSV2], ids=["qwen3", "dsv2"])
@pytest.mark.parametrize("tokens", [1, 8, 64, 256, 1024])
def test_routed_rows_never_exceed_what_a_kernel_must_do(conf, tokens):
    """The counted work is a floor for any kernel that computes the layer:
    it reads at least the touched pages and computes at least the routed
    rows, so time at peak for the counted work can never beat a real
    kernel (share <= 100%), while the padded work the program computes
    (every expert, capacity T rows) is E/k times the counted FLOPs."""
    E, k = gmm.experts(conf), conf["num_experts_per_tok"]
    D, F = conf["hidden_size"], conf["moe_intermediate_size"]
    flops, nbytes = gmm.call(conf, tokens)
    padded_flops = 6.0 * D * F * E * tokens
    assert flops * E / k == pytest.approx(padded_flops)
    t = gmm.touched(E, k, tokens)
    assert k <= t + 1e-9 and t <= min(E, tokens * k) + 1e-9
    assert nbytes <= E * 3 * D * F * 2 + 2 * tokens * k * D * 2


def test_paged_attention_hand_counts_qwen3():
    f, b = attn.decode(QWEN, [100, 300])
    assert f == 4 * 400 * 32 * 128
    assert b == 2 * 400 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2
    f, b = attn.chunk(QWEN, 256, 256)
    assert f == 4 * (256 * 256 + 256 * 257 / 2) * 32 * 128
    assert b == 2 * 512 * 4 * 128 * 2 + 2 * 256 * 32 * 128 * 2


def test_calls_per_tick_cover_every_layer():
    t = serve.Tick(0.0, 0.01, decode_ctx=[10, 20], prefill=[(0, 256)])
    assert len(gmm.calls(QWEN, t)) == 2 * 6          # decode + chunk, 6 MoE
    assert len(gmm.calls(DSV2, t)) == 2 * 7          # the dense layer has none
    assert len(attn.calls(QWEN, t)) == 2 * 6
    assert gmm.calls(QWEN, serve.Tick(0, 1, [], [])) == []


def test_model_flops_per_token_hand_count_qwen3():
    D, H, KVH, hd, V = 2048, 32, 4, 128, 151936
    attn_proj = 2 * (D * H * hd + 2 * D * KVH * hd + H * hd * D)
    ffn = 2 * D * 128 + 6 * D * 768 * 8
    per_layer = attn_proj + 4 * 1000 * H * hd + ffn
    assert arch(QWEN).token(QWEN, 1000, True) == pytest.approx(
        6 * per_layer + 2 * D * V)
    # a tick's count is its architecture's, token by token
    t = serve.Tick(0.0, 0.01, decode_ctx=[1000], prefill=[])
    assert step.tick(QWEN, t) == arch(QWEN).token(QWEN, 1000, True)
