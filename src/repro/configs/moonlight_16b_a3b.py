"""moonlight-16b-a3b — [hf:moonshotai/Moonlight-16B-A3B] (model_type
deepseek_v3).

27L d_model=2048 16H, latent attention (kv_lora_rank 512, q_lora_rank
null, qk_nope_head_dim 128, qk_rope_head_dim 64, v_head_dim 128), vocab
163840, rope_theta 50000. The first layer is dense (SwiGLU 11264 wide,
first_k_dense_replace 1); the other 26 hold 64 routed experts of width
1408, 6 per token, and 2 shared experts (folded into one FFN of width
2 * 1408). Router: sigmoid scores, noaux_tc with one group, a correction
bias that only selects, the chosen scores renormalised times 2.446.

`CHIP_SHARE` is one TPU v5e chip's share of a deployment (model-configs
guide §4). The deployment: federated full-parameter training, each
client's copy dividing every layer over 8 chips — each chip holds 8 of
the 64 routed experts (expert parallelism) and 20480 of the 163840
vocabulary rows, while attention, the dense layer, the shared experts and
the router are whole on every chip — and depth split over pipeline
stages. This chip holds the first stage: the dense layer and 5 MoE layers
(6 of 27), with the embedding and the LM head, and the first 8 experts of
each layer. Every width, the router's 64 outputs and its 6 experts per
token are as published.
"""
import dataclasses

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab=163840,
    attention_mixer="mla",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    num_experts=64,
    router_experts=64,
    experts_per_token=6,
    shared_expert_ff=2 * 1408,
    router="sigmoid",
    routed_scaling=2.446,
    first_dense_layers=1,
    dense_d_ff=11264,
    norm="rmsnorm",
    act="swiglu",
    rope_theta=50_000.0,
    max_seq=8192,
)

CHIP_SHARE = dataclasses.replace(CONFIG, num_layers=6, num_experts=8,
                                 vocab=20480)

# keys changed from the published config: (published, here)
REDUCED = {"num_layers": (27, 6), "num_experts": (64, 8),
           "vocab": (163840, 20480)}

# sizes set by hand, not given by the published config
ASSUMED = {
    "deployment": "8 chips share each layer (8 experts and 20480 vocabulary "
                  "rows each); depth in pipeline stages; this chip holds "
                  "the first stage (the dense layer and 5 MoE layers) and "
                  "the first 8 experts",
    "seq": "8192 tokens per sequence (the published context)",
    "batch": "2 sequences per client per step",
    "shift_dtype": "float32 DIANA shifts, as train.py keeps them",
}

# where the program departs from the published model
DEPARTURES = {
    "norm_eps": "1e-6 (published rms_norm_eps 1e-5)",
    "rope": "rotate-halves RoPE on the 64-wide parts (the published code "
            "permutes interleaved pairs first: with seeded weights a fixed "
            "permutation of the rope columns)",
    "aux_loss": "no sequence-level auxiliary loss (seq_aux; no coefficient "
                "is published)",
    "bias_update": "the correction bias is not updated from the experts' "
                   "load during training",
}
