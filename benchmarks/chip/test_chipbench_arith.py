"""The benchmark's arithmetic and data files, on the CPU with no chip."""
import importlib.util
import json
import math
import re
from pathlib import Path

import jax
import numpy as np
import pytest

import flops
import peaks
import reference
import weights

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((HERE / "configs").glob("*.json"))}


def test_stablelm_flops_by_hand():
    m = CONFIGS["stablelm-1.6b-chip"]["model"]
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert flops.matmul_params(m) == 8 * per_layer + 100352 * 2048
    assert flops.matmul_params(m) == 616_562_688
    attn = 6 * 8 * 32 * 64 * 2048
    assert flops.model_flops_per_token(m, 2048) == 6 * 616_562_688 + attn
    assert math.isclose(flops.model_flops_per_token(m, 2048), 3.9007e9,
                        rel_tol=1e-4)


def test_starcoder2_flops_by_hand():
    m = CONFIGS["starcoder2-15b-chip"]["model"]
    attn = 6144 * 6144 * 2 + 6144 * 512 * 2
    mlp = 2 * 6144 * 24576
    assert flops.matmul_params(m) == 2 * (attn + mlp) + 12288 * 6144


@pytest.mark.parametrize("name,count", [("stablelm-1.6b-chip", 822_153_216),
                                        ("starcoder2-15b-chip", 918_628_352)])
def test_reference_tree_is_the_programs(name, count):
    """Same leaves, shapes and dtypes, in the same order: the wire's
    per-leaf window draws depend on it."""
    from repro.models import transformer

    from cell import arch_config

    conf = CONFIGS[name]
    prog = jax.eval_shape(lambda: transformer.init_params(
        jax.random.key(0), arch_config(conf)))
    ref = reference.param_shapes(conf["model"])
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(ref)) == count


def test_diana_shift_bytes_by_hand():
    # 300 elements pad to 384; 7 f32 streams of 4 bytes each
    assert flops.diana_shift_bytes([(10, 30)]) == 28 * 384
    assert flops.diana_shift_bytes([(128,), (2, 64)]) == 28 * 256


def test_peaks_lookup():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_seed_key_takes_large_seeds():
    a = weights.seed_key(2 ** 40 + 7, 1)
    b = weights.seed_key(2 ** 40 + 7, 1)
    c = weights.seed_key(2 ** 40 + 8, 1)
    assert np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(c))


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def test_benchmark_entries_resolve():
    for cell in BENCH["workloads"]:
        assert NAME.fullmatch(cell["name"]) and len(cell["why"]) <= 200
        assert (HERE / "traffic" / f"{cell['traffic']}.json").exists()
        assert (HERE / "limits" / f"{cell['name']}.json").exists()
        assert cell["config"] in CONFIGS
    for conf in BENCH["configs"]:
        assert conf["file"] == f"benchmarks/chip/configs/{conf['name']}.json"
        assert sorted(conf["reduced"]) == sorted(
            CONFIGS[conf["name"]]["reduced"])
    for metric in BENCH["per_layer"]:
        path = HERE / "metrics" / f"{metric['name']}.py"
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)
