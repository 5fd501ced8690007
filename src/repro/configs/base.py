"""Model configuration system.

Every assigned architecture (and the paper's own evaluation models) is an
instance of :class:`ModelConfig`.  The config is a *complete* architectural
description — ``models/model.py`` builds init/apply functions from it with no
other inputs, and ``launch/dryrun.py`` derives input specs from it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

ArchType = str  # "dense" | "moe" | "ssm" | "hybrid" | "encoder" | "vlm"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: ArchType
    num_layers: int
    d_model: int
    vocab_size: int

    # ---- attention ----
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0                 # 0 -> d_model // num_heads
    qkv_bias: bool = False
    rope_fraction: float = 1.0        # fraction of head_dim that is rotary
                                      # (chatglm3 "2d rope" = 0.5, stablelm = 0.25)
    attn_window: Optional[int] = None # sliding-window attention (beyond-paper
                                      # variant enabling long_500k on dense archs)
    causal: bool = True               # False for encoder-only (hubert)

    # ---- feed-forward ----
    d_ff: int = 0                     # dense MLP hidden dim (SwiGLU)
    mlp_gated: bool = True            # SwiGLU vs plain GELU MLP

    # ---- norm ----
    norm_type: str = "rmsnorm"        # "rmsnorm" | "layernorm"

    # ---- MoE ----
    num_experts: int = 0              # routed experts (0 -> dense MLP)
    top_k: int = 0
    moe_d_ff: int = 0                 # per-expert hidden dim
    num_shared_experts: int = 0       # deepseek-style shared experts
    dense_residual: bool = False      # arctic: dense MLP in parallel with MoE
    first_k_dense: int = 0            # deepseek: first k layers use dense MLP
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01

    # ---- MLA (deepseek v2) ----
    use_mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0              # 0 -> full-rank q projection
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # ---- SSM (mamba2 / zamba2) ----
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 64

    # ---- hybrid (zamba2) ----
    attn_every: int = 0               # shared attention block every k ssm blocks

    # ---- vlm (llama 3.2 vision) ----
    cross_attn_every: int = 0         # cross-attn layer every k self-attn layers
    num_image_tokens: int = 0         # patch embeddings provided by stub frontend

    # ---- audio (hubert) ----
    num_frame_tokens: int = 0         # frame embeddings provided by stub frontend

    # ---- substrate ----
    dtype: str = "bfloat16"
    max_seq_len: int = 131072
    tie_embeddings: bool = False

    # ---- provenance ----
    source: str = ""                  # where the published widths come from
    omits: str = ""                   # published features the model lacks

    # -------------------------------------------------------------- derived
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def has_decode(self) -> bool:
        """Encoder-only architectures have no autoregressive decode step."""
        return self.arch_type != "encoder"

    @property
    def supports_long_context(self) -> bool:
        """True if a 500k-token decode is sub-quadratic / O(1)-state."""
        if self.arch_type in ("ssm", "hybrid"):
            return True
        return self.attn_window is not None

    @property
    def ssm_heads(self) -> int:
        return (self.ssm_expand * self.d_model) // self.ssm_head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    # Parameter count (embedding + blocks), used for roofline MODEL_FLOPS.
    def param_count(self, active_only: bool = False) -> int:
        D, H = self.d_model, self.num_heads
        hd = self.resolved_head_dim
        kvh = self.num_kv_heads
        n = 0
        n += self.vocab_size * D                      # embed
        if not self.tie_embeddings:
            n += self.vocab_size * D                  # lm head
        per_layer = 0
        # attention
        if self.arch_type not in ("ssm",):
            if self.use_mla:
                r, qr = self.kv_lora_rank, (self.q_lora_rank or 0)
                qk = self.qk_nope_dim + self.qk_rope_dim
                if qr:
                    per_attn = D * qr + qr * H * qk
                else:
                    per_attn = D * H * qk
                per_attn += D * (r + self.qk_rope_dim)          # kv down + k_rope
                per_attn += r * H * (self.qk_nope_dim + self.v_head_dim)
                per_attn += H * self.v_head_dim * D             # o proj
            else:
                per_attn = D * H * hd + 2 * D * kvh * hd + H * hd * D
        else:
            per_attn = 0
        # ffn
        ff_mult = 3 if self.mlp_gated else 2
        if self.is_moe:
            routed = self.num_experts * ff_mult * D * self.moe_d_ff
            active = self.top_k * ff_mult * D * self.moe_d_ff
            shared = self.num_shared_experts * ff_mult * D * self.moe_d_ff
            dense = ff_mult * D * self.d_ff if self.dense_residual else 0
            per_ffn = (active if active_only else routed) + shared + dense
            per_ffn += D * self.num_experts                     # router
        elif self.d_ff:
            per_ffn = ff_mult * D * self.d_ff
        else:
            per_ffn = 0
        # ssm
        per_ssm = 0
        if self.arch_type in ("ssm", "hybrid"):
            di, ds = self.d_inner, self.ssm_state
            nh = self.ssm_heads
            per_ssm = D * (2 * di + 2 * ds + nh) + di * self.ssm_conv + di * D
        if self.arch_type == "ssm":
            per_layer = per_ssm
        elif self.arch_type == "hybrid":
            per_layer = per_ssm  # shared attn counted once below
        else:
            per_layer = per_attn + per_ffn
        n += self.num_layers * per_layer
        if self.arch_type == "hybrid" and self.attn_every:
            # one shared attention+mlp block reused every attn_every layers
            n += (D * H * hd + 2 * D * kvh * hd + H * hd * D
                  + ff_mult * D * self.d_ff)
        if self.arch_type == "vlm" and self.cross_attn_every:
            n_cross = self.num_layers // self.cross_attn_every
            n += n_cross * (D * H * hd + 2 * D * kvh * hd + H * hd * D)
        return n


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One of the four assigned global input shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: Tuple[InputShape, ...] = (
    InputShape("train_4k", 4_096, 256, "train"),
    InputShape("prefill_32k", 32_768, 32, "prefill"),
    InputShape("decode_32k", 32_768, 128, "decode"),
    InputShape("long_500k", 524_288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A smoke-test-sized variant of the same architecture family
    (2 layers, d_model<=512, <=4 experts), per the reproduction brief."""
    small: dict = dict(
        num_layers=2,
        d_model=min(cfg.d_model, 256),
        vocab_size=min(cfg.vocab_size, 512),
        max_seq_len=1024,
    )
    if cfg.num_heads:
        small["num_heads"] = min(cfg.num_heads, 4)
        small["num_kv_heads"] = max(1, min(cfg.num_kv_heads,
                                           min(cfg.num_heads, 4)))
        small["head_dim"] = 64 if cfg.resolved_head_dim >= 64 else cfg.resolved_head_dim
    if cfg.d_ff:
        small["d_ff"] = min(cfg.d_ff, 512)
    if cfg.is_moe:
        small["num_experts"] = min(cfg.num_experts, 4)
        small["top_k"] = min(cfg.top_k, 2)
        small["moe_d_ff"] = min(cfg.moe_d_ff, 256)
        small["num_shared_experts"] = min(cfg.num_shared_experts, 1)
        small["first_k_dense"] = min(cfg.first_k_dense, 1)
    if cfg.use_mla:
        small["kv_lora_rank"] = min(cfg.kv_lora_rank, 64)
        small["q_lora_rank"] = min(cfg.q_lora_rank, 64) if cfg.q_lora_rank else 0
        small["qk_nope_dim"] = 32
        small["qk_rope_dim"] = 16
        small["v_head_dim"] = 32
        small["head_dim"] = 0
    if cfg.ssm_state:
        small["ssm_state"] = min(cfg.ssm_state, 16)
        small["ssm_head_dim"] = 32
        small["ssm_chunk"] = 16
    if cfg.attn_every:
        small["attn_every"] = 1
        small["num_layers"] = 2
    if cfg.cross_attn_every:
        small["cross_attn_every"] = 2
        small["num_image_tokens"] = 16
    if cfg.num_frame_tokens:
        small["num_frame_tokens"] = 64
    small["dtype"] = "float32"
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **small)
