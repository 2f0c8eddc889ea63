"""Compile every Pallas kernel for a described TPU v5e chip; no chip needed.

Each test lowers one kernel with interpret=False at a StableLM-1.6B leaf
shape and compiles it with the TPU compiler for one chip of a described
v5e:2x2 topology. The compiled program must hold the Mosaic kernel
(`tpu_custom_call`). Interpret mode accepts block shapes and VMEM use that
the chip's compiler refuses; these tests fail where the chip would.

Shapes are row views of the chip-share config's leaves at the paper's
k/d = 0.02: the embedding / LM head (100352, 2048) puts a 2000-row slab on
the wire, an MLP leaf (16384, 5632) a 320-row slab, and a single-layer MLP
leaf (2048, 5632) a 40-row slab — five 8-row blocks, an odd count.
The window write-back writes such slabs into whole leaves and slot rows.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.diana_shift import diana_shift_update
from repro.kernels.pack import pack_slab, unpack_reduce, unpack_slab
from repro.kernels.qsgd import qsgd_quantize
from repro.kernels.randk import (
    randk_compress,
    randk_decompress,
    randk_decompress_into,
    randk_mask,
)

F32, BF16, U8 = jnp.float32, jnp.bfloat16, jnp.uint8


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 topology. The compilation cache is
    off meanwhile: a program compiled for a described chip is written to it
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


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
