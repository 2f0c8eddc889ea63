"""Config registry: exact assigned hyper-parameters + shape support matrix."""
import pytest

from repro.configs import (
    ARCH_NAMES,
    INPUT_SHAPES,
    all_configs,
    get_chip_config,
    get_config,
    reduced,
    shape_supported,
)

# (layers, d_model, heads, kv, d_ff, vocab) exactly as assigned
ASSIGNED = {
    "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
    "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
    "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
    "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
    "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
    "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
    "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
    "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
    "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
    "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
}


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_assigned_hparams_exact(name):
    cfg = get_config(name)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab) == ASSIGNED[name]


def test_all_ten_archs_present():
    assert len(ARCH_NAMES) == 11
    assert set(ASSIGNED) == set(ARCH_NAMES)


def test_moe_routing_params():
    q = get_config("qwen2-moe-a2.7b")
    assert (q.num_experts, q.experts_per_token) == (60, 4)
    assert q.shared_expert_ff == 4 * 1408
    d = get_config("dbrx-132b")
    assert (d.num_experts, d.experts_per_token) == (16, 4)


def test_param_counts_in_expected_range():
    """Nameplate sizes within ~20% (sanity on the model definitions)."""
    expect = {
        "stablelm-1.6b": 1.6e9, "deepseek-67b": 67e9, "rwkv6-7b": 7e9,
        "hymba-1.5b": 1.5e9, "starcoder2-15b": 15e9, "qwen2-vl-2b": 2e9,
        "qwen2.5-32b": 32e9, "qwen2-moe-a2.7b": 14e9, "whisper-medium": 0.7e9,
        "dbrx-132b": 132e9, "moonlight-16b-a3b": 16e9,
    }
    for name, target in expect.items():
        n = get_config(name).param_count()
        assert 0.6 * target < n < 1.6 * target, f"{name}: {n:.3g} vs {target:.3g}"


def test_active_params_moe():
    d = get_config("dbrx-132b")
    assert d.active_param_count() < 0.45 * d.param_count()


def test_long_context_support_matrix():
    """long_500k runs exactly for the sub-quadratic archs (DESIGN.md)."""
    shape = INPUT_SHAPES["long_500k"]
    runnable = {n for n in ARCH_NAMES
                if shape_supported(get_config(n), shape)[0]}
    assert runnable == {"rwkv6-7b", "hymba-1.5b", "starcoder2-15b"}
    # every other shape runs for every arch
    for s in ("train_4k", "prefill_32k", "decode_32k"):
        for n in ARCH_NAMES:
            assert shape_supported(get_config(n), INPUT_SHAPES[s])[0]


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_reduced_within_smoke_budget(name):
    cfg = reduced(get_config(name))
    assert cfg.num_layers == 2
    assert cfg.d_model <= 512
    assert cfg.num_experts <= 4
    assert cfg.family == get_config(name).family


def test_vocab_padding():
    assert get_config("hymba-1.5b").padded_vocab() == 32016
    assert get_config("whisper-medium").padded_vocab() == 51872
    assert get_config("deepseek-67b").padded_vocab() == 102400  # already /16


def test_chip_share_cuts_only_the_listed_keys():
    """The chip-share cut keeps every published width; the keys it changes
    are exactly those its file lists under REDUCED, with their published
    values."""
    import dataclasses
    import importlib

    widths = {"stablelm-1.6b": ("stablelm_1_6b", (2048, 32, 5632, 100352)),
              "moonlight-16b-a3b": ("moonlight_16b_a3b",
                                    (2048, 16, 1408, 20480))}
    for arch, (module, kept) in widths.items():
        mod = importlib.import_module(f"repro.configs.{module}")
        full, chip = get_config(arch), get_chip_config(arch)
        changed = {f.name for f in dataclasses.fields(full)
                   if getattr(full, f.name) != getattr(chip, f.name)}
        assert changed == set(mod.REDUCED)
        for key, (published, here) in mod.REDUCED.items():
            assert (getattr(full, key), getattr(chip, key)) == (published,
                                                                here)
        assert (chip.d_model, chip.num_heads, chip.d_ff, chip.vocab) == kept
    chip = get_chip_config("moonlight-16b-a3b")
    # the router keeps its 64 outputs and 6 experts per token; MLA's ranks
    # and the dense layer's width are as published
    assert (chip.n_router, chip.experts_per_token, chip.kv_lora_rank,
            chip.qk_nope_head_dim, chip.qk_rope_head_dim, chip.v_head_dim,
            chip.dense_d_ff) == (64, 6, 512, 128, 64, 128, 11264)


def test_moonlight_counts_sixteen_billion_with_three_active():
    cfg = get_config("moonlight-16b-a3b")
    assert 15.5e9 < cfg.param_count() < 16.5e9
    assert 2.5e9 < cfg.active_param_count() < 3.5e9


def test_chip_config_refuses_archs_without_a_cut():
    with pytest.raises(ValueError, match="no chip-share cut"):
        get_chip_config("deepseek-67b")
    with pytest.raises(ValueError, match="unknown arch"):
        get_chip_config("no-such-arch")
