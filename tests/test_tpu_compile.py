"""Compile every Pallas kernel for a described TPU v5e chip; no chip needed.

Each test lowers one kernel with interpret=False at a StableLM-1.6B leaf
shape and compiles it with the TPU compiler for one chip of a described
v5e:2x2 topology. The compiled program must hold the Mosaic kernel
(`tpu_custom_call`). Interpret mode accepts block shapes and VMEM use that
the chip's compiler refuses; these tests fail where the chip would.

The expert layer's grouped matmuls compile at Moonlight's chip-share
widths. The wire's shapes are row views of the chip-share config's leaves
at the paper's k/d = 0.02: the embedding / LM head (100352, 2048) puts a 2000-row slab on
the wire, an MLP leaf (16384, 5632) a 320-row slab, and a single-layer MLP
leaf (2048, 5632) a 40-row slab — five 8-row blocks, an odd count.
The window write-back writes such slabs into whole leaves and slot rows.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config, reduced
from repro.core.dist import CompressedAggregation
from repro.kernels.diana_shift import diana_shift_update
from repro.kernels.pack import pack_slab, unpack_reduce, unpack_slab
from repro.kernels.qsgd import qsgd_quantize
from repro.kernels.randk import (
    randk_compress,
    randk_decompress,
    randk_decompress_into,
    randk_mask,
)
from repro.launch import steps
from repro.models.moe import moe_gmm, moe_tgmm

F32, BF16, U8 = jnp.float32, jnp.bfloat16, jnp.uint8


@pytest.fixture(scope="module")
def topology():
    """A described v5e:2x2 topology. The compilation cache is off
    meanwhile: a program compiled for a described chip is written to it
    but cannot be read back without the chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topology):
    """One chip of the described topology."""
    return SingleDeviceSharding(topology.devices[0])


def compile_for_chip(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,d,kb", [(100352, 2048, 250), (16384, 5632, 40)])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_randk_compress(one_chip, n, d, kb, dtype):
    compile_for_chip(
        lambda rows, s: randk_compress(rows, s, k_blocks=kb, interpret=False),
        one_chip, ((n, d), dtype), ((), jnp.int32))


@pytest.mark.parametrize("n,d,kb", [(100352, 2048, 250), (16384, 5632, 40)])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_randk_decompress(one_chip, n, d, kb, dtype):
    compile_for_chip(
        lambda vals, s: randk_decompress(vals, s, n_rows=n, interpret=False),
        one_chip, ((kb * 8, d), dtype), ((), jnp.int32))


@pytest.mark.parametrize("r,n,d,kb", [(100352, 100352, 2048, 250),
                                      (8 * 2048, 2048, 5632, 5),
                                      (2048, 2048, 1, 5)])
@pytest.mark.parametrize("dtype", [F32, BF16])
def test_randk_decompress_into(one_chip, r, n, d, kb, dtype):
    """The window write-back, in place: a plain leaf, one slot row of a
    stacked table, and a 1-D leaf's (rows, 1) view."""
    compile_for_chip(
        lambda into, vals, s, b: randk_decompress_into(
            into, vals, s, b, n_rows=n, interpret=False),
        one_chip, ((r, d), dtype), ((kb * 8, d), F32), ((), jnp.int32),
        ((), jnp.int32))


def test_randk_mask(one_chip):
    d = 2048 * 5632
    compile_for_chip(
        lambda x, s: randk_mask(x, s, d=d, k=d // 50, interpret=False),
        one_chip, ((4, d), F32), ((4,), jnp.int32))


def test_qsgd_quantize(one_chip):
    n = 2048 * 5632
    compile_for_chip(
        lambda x, u: qsgd_quantize(x, u, levels=8, interpret=False),
        one_chip, ((n,), F32), ((n,), F32))


def test_diana_shift_update(one_chip):
    n = 100352 * 2048
    compile_for_chip(
        lambda h, qo, mh, qm: diana_shift_update(h, qo, mh, qm, alpha=0.5,
                                                 interpret=False),
        one_chip, *[((n,), F32)] * 4)


SLABS = [(2000, 2048), (320, 5632), (40, 5632)]


@pytest.mark.parametrize("k,d", SLABS)
@pytest.mark.parametrize("nibble", [False, True])
def test_pack_slab(one_chip, k, d, nibble):
    levels = 7 if nibble else 127
    compile_for_chip(
        lambda v, u: pack_slab(v, u, levels=levels, nibble=nibble,
                               interpret=False),
        one_chip, ((k, d), F32), ((k, d), F32))


@pytest.mark.parametrize("k,d", SLABS)
@pytest.mark.parametrize("nibble", [False, True])
def test_unpack_slab(one_chip, k, d, nibble):
    levels = 7 if nibble else 127
    compile_for_chip(
        lambda p, s: unpack_slab(p, s, levels=levels, n_rows=k,
                                 nibble=nibble, interpret=False),
        one_chip, ((k // 2 if nibble else k, d), U8), ((k, 1), F32))


@pytest.mark.parametrize("k,d", SLABS)
@pytest.mark.parametrize("nibble", [False, True])
def test_unpack_reduce(one_chip, k, d, nibble):
    levels = 7 if nibble else 127
    ranks = 4
    compile_for_chip(
        lambda p, s: unpack_reduce(p, s, levels=levels, n_rows=k,
                                   nibble=nibble, interpret=False),
        one_chip, ((ranks, k // 2 if nibble else k, d), U8),
        ((ranks, k, 1), F32))


# Moonlight's chip share: 2 x 8192 tokens x 6 copies sorted by expert, 8
# held experts of width 1408 over d_model 2048, and one group of the rows
# routed elsewhere. Each product in both orientations (gate/up: 2048 ->
# 1408, down: 1408 -> 2048), as the forward pass and the input cotangent
# run them, and the weight cotangents.
ROWS, HELD = 2 * 8192 * 6, 8


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
@pytest.mark.parametrize("transpose", [False, True])
def test_moe_gmm(one_chip, k, n, transpose):
    rhs = (HELD, n, k) if transpose else (HELD, k, n)
    compile_for_chip(
        lambda x, w, gs: moe_gmm(x, w, gs, transpose_rhs=transpose),
        one_chip, ((ROWS, k), BF16), (rhs, BF16),
        ((HELD + 1,), jnp.int32))


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_moe_tgmm(one_chip, k, n):
    compile_for_chip(
        lambda x_t, g, gs: moe_tgmm(x_t, g, gs, HELD),
        one_chip, ((k, ROWS), BF16), ((ROWS, n), BF16),
        ((HELD + 1,), jnp.int32))


def moonlight_step_hlo(topology, shape, monkeypatch, *, held=4, seq=128):
    """The reduced Moonlight train step (diana, shared f32 wire) compiled
    for the described chips as a ("data", "model") mesh of `shape`, one
    client per data rank of 2 x `seq` tokens, holding `held` of the
    router's 8 experts, with the TPU's kernels."""
    key = jax.random.key(0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(
        reduced(get_config("moonlight-16b-a3b"), seq=seq), num_experts=held)
    devices = np.asarray(topology.devices[:math.prod(shape)])
    mesh = Mesh(devices.reshape(shape), ("data", "model"))
    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.02,
                                mean_scale=1.0, shift_dtype=F32,
                                wire_dtype="f32", wire_levels=None)
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.02, remat="full")
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    tokens = jax.ShapeDtypeStruct((2 * shape[0], seq + 1), jnp.int32)
    batch = {"tokens": jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=batch_sh({"tokens": tokens})["tokens"])}
    with jax.set_mesh(mesh):
        return jitted.lower(state, batch, key).compile().as_text()


def test_moonlight_clients_run_their_experts_on_their_own_chips(
        topology, monkeypatch):
    """Four clients on four chips: each chip runs its own client's expert
    kernels, and the gradients gather no expert weights or token copies
    from other chips."""
    hlo = moonlight_step_hlo(topology, (4, 1), monkeypatch)
    assert re.search(r"%moe_gmm[.\d]* = ", hlo)
    assert re.search(r"%moe_tgmm[.\d]* = ", hlo)
    gathers = [line for line in hlo.splitlines()
               if "all-gather" in line and "client_grads" in line]
    assert not gathers, gathers[:3]


def test_moonlight_step_runs_its_experts_on_a_block_of_the_copies(
        topology, monkeypatch):
    """One expert of the router's 8 held, 2 x 256 tokens x 2 copies: the
    held experts' copies run in 512-row chunks, and nothing the expert
    layer computes has a row for every one of the 1024 copies."""
    hlo = moonlight_step_hlo(topology, (1, 1), monkeypatch, held=1, seq=256)
    kernels = [line for line in hlo.splitlines()
               if re.search(r"%moe_t?gmm[.\d]* = ", line)]
    assert kernels and all("client_grads" in line for line in kernels)
    moe = [line for line in hlo.splitlines()
           if re.search(r'op_name="[^"]*moe_(dispatch|experts)', line)]
    assert any(re.search(r"\[(1,)?512,128\]", line) for line in moe)
    copies = [line for line in moe
              if re.search(r"\[(1,)?1024,(128|256)\]", line)]
    assert not copies, copies[:3]


def test_moonlight_experts_split_over_model_are_refused(
        topology, monkeypatch):
    with pytest.raises(NotImplementedError, match="whole experts"):
        moonlight_step_hlo(topology, (2, 2), monkeypatch)
