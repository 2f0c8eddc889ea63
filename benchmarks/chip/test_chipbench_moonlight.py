"""The Moonlight cell's path at a tiny size on the CPU, and its counts by
hand: a sound run is `correct`, the faults and the control are caught,
and the FLOP, parameter and expert-kernel counts are what the widths give.

`chipbench_tiny.tiny_config` cuts only the dense widths, so the latent
attention's ranks and the experts get a tiny configuration of their own
here: 1 dense and 1 expert layer, 4 of the router's 8 experts held, 2 per
token."""
import importlib.util
import json
import time
from pathlib import Path

import jax
import pytest

import cell
import compare
import reference
from chipbench_tiny import TINY_LIMITS, tiny_traffic
from control import VARIANTS

HERE = Path(__file__).resolve().parent
CONF = json.loads((HERE / "configs" / "moonlight-16b-a3b-chip.json")
                  .read_text())
TINY = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=4,
            kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, d_ff=64, vocab=512, num_experts=4,
            router_experts=8, experts_per_token=2, shared_expert_ff=128,
            dense_d_ff=256)
SEED = 2 ** 33 + 23


def tiny_moonlight() -> dict:
    return {"name": "tiny-moonlight", "arch": CONF["arch"],
            "overrides": TINY, "model": {**CONF["model"], **TINY},
            "reference": CONF["reference"]}


def load(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_tiny(fault=None):
    return cell.run(tiny_moonlight(), tiny_traffic("diana.full.s8k"),
                    TINY_LIMITS, seed=SEED, seconds=0.0,
                    devices=jax.devices()[:1], t_start=time.perf_counter(),
                    fault=fault)


@pytest.fixture(scope="module")
def sound():
    return run_tiny()


def test_sound_run_is_correct_and_the_control_fails(sound):
    """The program passes; the reference on float8 operands and on half
    the rows, in its place (as `control.py` reads them), fail."""
    assert sound["correct"] and sound["finite"], sound["checks"]
    ref = sound["readings"]["reference"]
    model = cell.reference_model(tiny_moonlight())
    for name, kw in VARIANTS.items():
        got = reference.run(model, tiny_moonlight()["model"], *sound["keys"],
                            sound["feeds"], **sound["wire"], **kw)
        gaps = compare.gaps(got, ref)
        assert not compare.judge(gaps, TINY_LIMITS)[0], (name, gaps)


@pytest.mark.parametrize("fault", cell.FAULTS)
def test_fault_is_caught(fault, sound, monkeypatch):
    """The broken step against the sound run's reference readings: the
    faults keep the rows fed and the keys, so the reference is the same
    and is not computed again."""
    def same_reference(model, m, wkey, rkey, feeds, **kw):
        assert all((a == b).all() for a, b in zip(feeds, sound["feeds"]))
        return sound["readings"]["reference"]

    monkeypatch.setattr(cell.reference, "run", same_reference)
    rec = run_tiny(fault)
    assert rec["finite"]
    assert not rec["correct"], rec["checks"]


def test_flops_and_params_by_hand():
    ref, m = cell.reference_model(CONF), CONF["model"]
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    assert mla == 13_762_560
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + 3 * 2048 * 2816 + expert * 6 * 8 // 64
    head = 20480 * 2048
    assert ref.matmul_params(m) == (mla + 3 * 2048 * 11264) \
        + 5 * (mla + moe) + head == 313_327_616
    attn = 6 * 16 * (192 + 128) * (8192 // 2) * 6
    assert ref.flops_per_token(m, 8192) == 6 * 313_327_616 + attn
    assert ref.flops_per_token(m, 8192) == pytest.approx(2.635e9, rel=1e-3)
    norms = 2 * 2048
    dense = mla + 512 + 3 * 2048 * 11264 + norms
    moe_layer = mla + 512 + 2048 * 64 + 64 + 8 * expert \
        + 3 * 2048 * 2816 + norms
    assert moe_layer == 100_405_824
    assert CONF["param_count"] == 2 * 20480 * 2048 + dense + 5 * moe_layer \
        + 2048 == 668_890_432


def test_expert_kernel_count_and_readers_by_hand():
    ref, m = cell.reference_model(CONF), CONF["model"]
    rows = 16384 * 6 * 8 / 64  # 1536 a held expert
    assert rows == 12288
    flops, nbytes = ref.expert_kernel_work(m, 16384)
    assert flops == 5 * 12 * 2 * rows * 2048 * 1408
    assert nbytes == 5 * 12 * 2 * (8 * 2048 * 1408 + rows * (2048 + 1408))
    device_ms = load(HERE / "metrics" / "experts_device_ms.py")
    roofline = load(HERE / "metrics" / "experts_roofline.py")
    record = {"rounds": 10, "chips": 1, "tokens": 10 * 16384,
              "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    ops = {"moe_gmm.3": 0.25, "moe_gmm.17": 0.15, "moe_tgmm.2": 0.1,
           "fusion.12": 9.0}
    trace = {"chips": [{"op_s": ops}]}
    assert device_ms.read(record, trace) == pytest.approx(50.0)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == flops / 197e12  # compute-bound at 541 FLOPs a byte
    # the reader counts the work of the cell that `--workload` names
    argv = ["--workload", "moonlight.diana.s8k", "--seed", "1"]
    assert roofline.cell_config(argv) == CONF
    assert roofline.read(record, trace, argv) == pytest.approx(
        100 * least / 0.05)
    # a dense cell's reference counts no expert work
    assert roofline.read(record, trace, ["--workload",
                                         "stablelm.diana.full"]) is None
    # the parent program runs no expert kernel: both fall silent
    silent = {"chips": [{"op_s": {"fusion.12": 9.0}}]}
    assert device_ms.read(record, silent) is None
    assert roofline.read(record, silent, argv) is None
