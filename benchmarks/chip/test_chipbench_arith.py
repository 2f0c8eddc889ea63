"""The benchmark's arithmetic and data files, on the CPU with no chip."""
import importlib.util
import json
import math
import re
from pathlib import Path

import jax
import numpy as np
import pytest

import peaks
import weights
from cell import reference_model

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((HERE / "configs").glob("*.json"))}


def test_stablelm_flops_by_hand():
    conf = CONFIGS["stablelm-1.6b-chip"]
    dense, m = reference_model(conf), conf["model"]
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert dense.matmul_params(m) == 8 * per_layer + 100352 * 2048
    assert dense.matmul_params(m) == 616_562_688
    attn = 6 * 8 * 32 * 64 * 2048
    assert dense.flops_per_token(m, 2048) == 6 * 616_562_688 + attn
    assert math.isclose(dense.flops_per_token(m, 2048), 3.9007e9,
                        rel_tol=1e-4)


def test_starcoder2_flops_by_hand():
    conf = CONFIGS["starcoder2-15b-chip"]
    dense, m = reference_model(conf), conf["model"]
    attn = 6144 * 6144 * 2 + 6144 * 512 * 2
    mlp = 2 * 6144 * 24576
    assert dense.matmul_params(m) == 2 * (attn + mlp) + 12288 * 6144
    assert dense.flops_per_token(m, 2048) == 6 * dense.matmul_params(m) \
        + 6 * 2 * 48 * 128 * 2048


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_tree_is_the_programs(name):
    """The reference model each configuration file names has the program's
    tree: same leaves, shapes and dtypes, in the same order (the wire's
    per-leaf window draws depend on it), and as many parameters as the file
    counts by hand (`param_count`, required)."""
    from repro.models import transformer

    from cell import arch_config

    conf = CONFIGS[name]
    prog = jax.eval_shape(lambda: transformer.init_params(
        jax.random.key(0), arch_config(conf)))
    ref = reference_model(conf).param_shapes(conf["model"])
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert "param_count" in conf, f"{name} states no param_count"
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(ref)) == conf["param_count"]


@pytest.mark.parametrize("reference,message", [
    (None, "names no reference model"),
    ("no_such_model", "'no_such_model' does not exist"),
])
def test_config_without_its_reference_fails_at_load(reference, message):
    conf = {k: v for k, v in CONFIGS["stablelm-1.6b-chip"].items()
            if k != "reference"}
    if reference is not None:
        conf["reference"] = reference
    with pytest.raises(ValueError, match=message):
        reference_model(conf)


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


def test_stall_ms_by_hand():
    spec = importlib.util.spec_from_file_location(
        "stall_ms", HERE / "metrics" / "stall_ms.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # median 0.31 s; the rounds above it exceed it by 2 ms and 400 ms
    times = [0.30, 0.31, 0.31, 0.312, 0.71]
    assert mod.read({"round_s": times}, None) == pytest.approx(402.0)
    assert mod.read({"round_s": [0.31]}, None) is None
