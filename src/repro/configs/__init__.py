"""Architecture registry: the 11 configs + reduced smoke variants.

Usage:
    cfg = get_config("deepseek-67b")
    small = reduced(cfg)            # 2 layers, d_model<=512, <=4 experts
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.configs.shapes import (
    INPUT_SHAPES,
    InputShape,
    input_specs,
    shape_supported,
    train_batch_specs,
)
from repro.models.config import ArchConfig

_MODULES = {
    "stablelm-1.6b": "stablelm_1_6b",
    "deepseek-67b": "deepseek_67b",
    "rwkv6-7b": "rwkv6_7b",
    "hymba-1.5b": "hymba_1_5b",
    "starcoder2-15b": "starcoder2_15b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "qwen2.5-32b": "qwen2_5_32b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "whisper-medium": "whisper_medium",
    "dbrx-132b": "dbrx_132b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str) -> ArchConfig:
    try:
        mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    except KeyError:
        raise ValueError(f"unknown arch {name!r}; options: {sorted(_MODULES)}")
    return mod.CONFIG


def get_chip_config(name: str) -> ArchConfig:
    """The arch's chip-share cut (published widths, depth cut to one TPU
    v5e chip's share); only archs whose config file defines CHIP_SHARE
    have one."""
    get_config(name)
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    if not hasattr(mod, "CHIP_SHARE"):
        have = [n for n in ARCH_NAMES if hasattr(importlib.import_module(
            f"repro.configs.{_MODULES[n]}"), "CHIP_SHARE")]
        raise ValueError(f"{name!r} has no chip-share cut yet; archs with "
                         f"one: {have}")
    return mod.CHIP_SHARE


def all_configs() -> dict[str, ArchConfig]:
    return {n: get_config(n) for n in ARCH_NAMES}


def reduced(cfg: ArchConfig, *, seq: int = 64) -> ArchConfig:
    """Same-family reduced variant for CPU smoke tests:
    2 layers (one of them dense where the model leads with dense layers),
    d_model <= 512, <= 4 experts held (of 8 router outputs where the
    router is wider than the experts held), tiny vocab/window/latent."""
    heads = 4
    head_dim = 32
    d_model = heads * head_dim  # 128 — rwkv needs d % heads == 0
    kv = max(1, round(heads * cfg.num_kv_heads / cfg.num_heads))
    changes = dict(
        num_layers=2,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=head_dim,
        d_ff=256,
        vocab=503,  # deliberately pad-worthy (503 -> 512)
        max_seq=max(seq * 2, 128),
    )
    if cfg.num_experts:
        changes.update(num_experts=4, experts_per_token=2)
        if cfg.shared_expert_ff:
            changes.update(shared_expert_ff=128)
        if cfg.router_experts:
            # the router's outputs outnumber the experts held, as on a chip
            changes.update(router_experts=8)
    if cfg.attention_mixer == "mla":
        changes.update(kv_lora_rank=64, qk_nope_head_dim=32,
                       qk_rope_head_dim=16, v_head_dim=32)
    if cfg.first_dense_layers:
        changes.update(first_dense_layers=1, dense_d_ff=384)
    if cfg.ssm_state:
        changes.update(ssm_state=8, ssm_heads=heads)
    if cfg.sliding_window:
        changes.update(sliding_window=min(cfg.sliding_window, seq // 2))
    if cfg.encoder_layers:
        changes.update(encoder_layers=2, encoder_seq=24)
    if cfg.vision_patches:
        changes.update(vision_patches=16)
    if cfg.mrope_sections is not None:
        changes.update(mrope_sections=(4, 6, 6))  # head_dim/2 = 16 channels
    return dataclasses.replace(cfg, **changes)


__all__ = [
    "ARCH_NAMES",
    "ArchConfig",
    "INPUT_SHAPES",
    "InputShape",
    "all_configs",
    "get_chip_config",
    "get_config",
    "input_specs",
    "reduced",
    "shape_supported",
    "train_batch_specs",
]
