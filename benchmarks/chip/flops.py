"""Operations and bytes the benchmark counts, from shapes alone.

Model FLOPs (the `mfu` convention): a trained token costs 6 FLOPs per
parameter it meets in a matrix multiplication (2 forward, 4 backward),
plus causal attention: per layer and token, QK^T and PV over on average
seq/2 keys are 2 * 2 * (seq/2) * heads * head_dim FLOPs forward, times 3
with the backward. The embedding lookup, norms, biases, the loss and any
recomputation under rematerialisation are not counted.

Kernel bytes: what each Pallas kernel must move through HBM per call,
from the shapes it is called with.
"""
from __future__ import annotations

import math

LANES = 128  # the DIANA kernel's lane width: leaves pad to a multiple


def matmul_params(m: dict) -> int:
    """Parameters met in matmuls per token: attention and MLP of every
    layer, and the LM head. `m` is the configuration's model section."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    mlp = (3 if m["act"] == "swiglu" else 2) * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + m["vocab"] * d


def attention_flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * seq


def model_flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * matmul_params(m) + attention_flops_per_token(m, seq)


def diana_shift_bytes(leaf_shapes) -> int:
    """One round of the fused DIANA update over every leaf: four f32
    inputs read (h, Q_own, H, Q_mean) and three f32 outputs written
    (direction, h', H'), each leaf padded to a multiple of 128."""
    n = sum(-(-math.prod(s) // LANES) * LANES for s in leaf_shapes)
    return 7 * 4 * n
