"""stablelm-1.6b — [hf:stabilityai/stablelm-2-1_6b].

24L d_model=2048 32H (GQA kv=32, i.e. MHA) d_ff=5632 vocab=100352.
StableLM-2 uses LayerNorm + SwiGLU + (partial) RoPE; we apply full-dim RoPE.

`CHIP_SHARE` is one TPU v5e chip's share of a deployment (model-configs
guide §4). The deployment: federated full-parameter training of the whole
24-layer model, each client's copy split depth-wise into three pipeline
stages of 8 layers on three chips; one chip holds one stage. Every width is
as published; only the depth is cut (`REDUCED`), and the embedding and the
LM head both stay, so the stage runs end to end. What the cut leaves on the
chip is the state a stage holds in the deployment: bf16 weights, the f32
DIANA shift of its client and the f32 mean shift, plus the activations of a
remat'd step. 8 layers is the deepest cut that fits the 16 GB chip: the
described-chip compile of the diana step (f32 shifts, full remat, seq 2048,
2 sequences per client) puts it at about 13 GiB.
"""
import dataclasses

from repro.models.config import ArchConfig

CONFIG = ArchConfig(
    name="stablelm-1.6b",
    family="dense",
    num_layers=24,
    d_model=2048,
    num_heads=32,
    num_kv_heads=32,
    d_ff=5632,
    vocab=100352,
    norm="layernorm",
    act="swiglu",
    rope_theta=10_000.0,
)

CHIP_SHARE = dataclasses.replace(CONFIG, num_layers=8)

# keys changed from the published config: (published, here)
REDUCED = {"num_layers": (24, 8)}

# sizes set by hand, not given by the published config
ASSUMED = {
    "pipeline_stages": "3 stages of 8 layers per client; one per chip",
    "seq": "2048 tokens per sequence (published context 4096)",
    "batch": "2 sequences per client per step",
    "shift_dtype": "float32 DIANA shifts, as train.py keeps them",
}
