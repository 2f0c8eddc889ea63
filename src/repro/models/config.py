"""Architecture configuration shared by every model family."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'ssm' | 'hybrid' | 'vlm' | 'audio'
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None

    # attention
    rope_theta: float = 1e4
    qkv_bias: bool = False
    sliding_window: int | None = None
    mrope_sections: tuple[int, int, int] | None = None  # (t, h, w) — qwen2-vl
    attention_mixer: str = "attn"  # 'attn' | 'mla' | 'rwkv6' | 'hymba'
    # latent attention (mla, DeepSeek-V2/V3 with q_lora_rank null): keys and
    # values come from a normalised kv_lora_rank-wide latent; each head's
    # query/key is qk_nope_head_dim wide plus a qk_rope_head_dim-wide
    # rotated part (the key's shared by the heads), its value v_head_dim
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # ffn
    act: str = "swiglu"  # 'swiglu' | 'gelu'
    num_experts: int = 0
    experts_per_token: int = 0
    shared_expert_ff: int = 0  # qwen2-moe shared experts as one fused FFN
    # the router's outputs; 0 = num_experts. Larger when this chip holds a
    # share of the experts: num_experts is then how many it holds (the
    # first num_experts of the router's)
    router_experts: int = 0
    # 'softmax': top-k of softmax probabilities, renormalised (qwen2-moe);
    # 'sigmoid': DeepSeek-V3's noaux_tc — top-k of sigmoid scores plus a
    # selection-only bias, weighted by the unbiased scores, renormalised,
    # times routed_scaling
    router: str = "softmax"
    routed_scaling: float = 1.0
    # leading dense SwiGLU layers of width dense_d_ff before the expert
    # stack (DeepSeek's first_k_dense_replace); counted in num_layers
    first_dense_layers: int = 0
    dense_d_ff: int = 0

    # ssm / hybrid
    ssm_state: int = 0
    ssm_heads: int = 0  # hymba: number of parallel mamba heads

    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0  # stub frame count (1500 for whisper)

    # vlm stub
    vision_patches: int = 0  # patches consumed per sample at train/prefill

    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm'
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    # training shape defaults (overridden by input-shape presets)
    max_seq: int = 4096

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.attention_mixer == "rwkv6"

    def padded_vocab(self, multiple: int = 16) -> int:
        """Vocab padded for TP divisibility (Megatron practice); logits at pad
        ids are masked so the math is unchanged."""
        return ((self.vocab + multiple - 1) // multiple) * multiple

    def supports_long_context(self) -> bool:
        """Sub-quadratic decode path exists (DESIGN.md §Arch-applicability)."""
        return self.attention_mixer in ("rwkv6", "hymba") or self.sliding_window is not None

    @property
    def n_router(self) -> int:
        """The router's outputs (the experts of the whole layer)."""
        return self.router_experts or self.num_experts

    def param_count(self) -> int:
        """Approximate total parameters (embedding + blocks), for 6ND."""
        d, f, hd = self.d_model, self.d_ff, self.head_dim
        qh, kh = self.num_heads, self.num_kv_heads
        attn = d * qh * hd + 2 * d * kh * hd + qh * hd * d
        if self.attention_mixer == "mla":
            r, qk = self.kv_lora_rank, self.qk_nope_head_dim
            attn = (d * qh * (qk + self.qk_rope_head_dim)
                    + d * (r + self.qk_rope_head_dim) + r
                    + r * qh * (qk + self.v_head_dim)
                    + qh * self.v_head_dim * d)
        elif self.attention_mixer == "rwkv6":
            # r,k,v,g,w projections + output
            attn = 6 * d * d
        elif self.attention_mixer == "hymba":
            ssm_inner = self.ssm_heads * hd
            attn += 2 * d * ssm_inner + ssm_inner * d + ssm_inner * (2 * self.ssm_state + 2)
        if self.num_experts:
            ffn = self.num_experts * (3 if self.act == "swiglu" else 2) * d * f
            ffn += d * self.n_router
            if self.router == "sigmoid":
                ffn += self.n_router  # the selection bias
            if self.shared_expert_ff:
                ffn += (3 if self.act == "swiglu" else 2) * d * self.shared_expert_ff
        else:
            ffn = (3 if self.act == "swiglu" else 2) * d * f
        per_layer = attn + ffn + 2 * d
        total = self.num_layers * per_layer + self.vocab * d
        if self.first_dense_layers:
            dense = attn + 3 * d * self.dense_d_ff + 2 * d
            total += self.first_dense_layers * (dense - per_layer)
        if not self.tie_embeddings:
            total += self.vocab * d
        if self.is_encdec:
            enc_attn = 4 * d * d
            enc_ffn = (3 if self.act == "swiglu" else 2) * d * f
            total += self.encoder_layers * (enc_attn + enc_ffn + 2 * d)
            total += self.num_layers * (4 * d * d)  # cross-attn in decoder
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: routed top-k + shared only)."""
        if not self.num_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        n_ff_mats = 3 if self.act == "swiglu" else 2
        dense_ffn = self.num_experts * n_ff_mats * d * f
        active_ffn = self.experts_per_token * n_ff_mats * d * f
        moe_layers = self.num_layers - self.first_dense_layers
        return self.param_count() - moe_layers * (dense_ffn - active_ffn)
