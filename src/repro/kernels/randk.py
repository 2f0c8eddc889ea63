"""Pallas TPU kernels: circular row-block gather/scatter (Rand-k wire).

The production compressor (core/dist.py) selects a circular block of rows
from the (n_rows, D) view of each gradient leaf. On GPU this is a gather
kernel over scattered indices; on TPU the natural unit is a *block-aligned*
circular window — the gather becomes `k_blocks` sequential VMEM copies whose
source block index is computed from a prefetched scalar (`start_block`), so
the whole compression is one HBM read of k rows, no index lists.

  randk_compress:   rows (N, D), start -> (K, D) * (N/K)   [gather+scale]
  randk_decompress: vals (K, D), start -> (N, D) zeros elsewhere [scatter]
  randk_decompress_into: vals (K, D), start -> into (R, D), the window's
                    rows overwritten in place, the rest kept [write-back]
  randk_mask:       x (M, Dp), starts (M,) -> dense Q(x) per client

`randk_mask` is the simulator-side fused compress+decompress (DESIGN.md
§3.5): the algorithms' math consumes the dense reconstruction Q(x), and for
a circular-window Rand-k that is just a masked scale — one elementwise pass,
batched over all M clients in a single launch, each client with its own
prefetched window start. No gather, no scatter, no per-leaf loop.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 8  # sublane-aligned row block


def _gather_kernel(start_ref, x_ref, o_ref, *, scale: float):
    del start_ref  # consumed by the index_map
    o_ref[...] = (x_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("k_blocks", "block_rows", "interpret"))
def randk_compress(rows: jax.Array, start_block: jax.Array, *, k_blocks: int,
                   block_rows: int = BLOCK_ROWS,
                   interpret: bool | None = None) -> jax.Array:
    """rows: (N, D), N % block_rows == 0. Returns (k_blocks*block_rows, D)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n, d = rows.shape
    nb = n // block_rows
    scale = nb / k_blocks

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i, start: ((start[0] + i) % nb, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i, start: (i, 0)),
    )
    return pl.pallas_call(
        partial(_gather_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k_blocks * block_rows, d), rows.dtype),
        interpret=interpret,
    )(start_block.reshape(1).astype(jnp.int32), rows)


def _scatter_kernel(start_ref, vals_ref, o_ref, *, k_blocks: int, nb: int):
    j = pl.program_id(0)
    # offset of this output block inside the circular window (or >= k_blocks
    # if the block is outside the window and must stay zero)
    off = jax.lax.rem(j - start_ref[0] + nb, nb)
    inside = off < k_blocks
    o_ref[...] = jnp.where(inside, vals_ref[...], 0.0).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("n_rows", "block_rows", "interpret"))
def randk_decompress(vals: jax.Array, start_block: jax.Array, *, n_rows: int,
                     block_rows: int = BLOCK_ROWS,
                     interpret: bool | None = None) -> jax.Array:
    """vals: (K, D) -> (n_rows, D), zero outside the circular window."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    k, d = vals.shape
    kb = k // block_rows
    nb = n_rows // block_rows

    def val_index(j, start):
        off = jax.lax.rem(j - start[0] + nb, nb)
        return (jnp.minimum(off, kb - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, d), val_index)],
        out_specs=pl.BlockSpec((block_rows, d), lambda j, start: (j, 0)),
    )
    return pl.pallas_call(
        partial(_scatter_kernel, k_blocks=kb, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, d), vals.dtype),
        interpret=interpret,
    )(start_block.reshape(1).astype(jnp.int32), vals)


def _write_kernel(scalars_ref, vals_ref, into_ref, o_ref):
    del scalars_ref, into_ref  # the index_map places the block; o aliases into
    o_ref[...] = vals_ref[...].astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("n_rows", "block_rows", "interpret"))
def randk_decompress_into(into: jax.Array, vals: jax.Array,
                          start_block: jax.Array, base_block: jax.Array, *,
                          n_rows: int, block_rows: int = BLOCK_ROWS,
                          interpret: bool | None = None) -> jax.Array:
    """Write the (K, D) window `vals` into `into` (R, D) in place.

    The window is the circular one `randk_compress` gathers, inside the
    `n_rows` rows that start at block `base_block` (a slot row of a
    stacked table; 0 for a plain leaf): block i of `vals` lands on block
    base_block + (start_block + i) % (n_rows / block_rows). Every other row
    of `into` keeps its value — the output aliases it, so only the K rows
    move through VMEM.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    k, d = vals.shape
    kb = k // block_rows
    nb = n_rows // block_rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kb,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i, s: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block_rows, d),
                               lambda i, s: (s[1] + (s[0] + i) % nb, 0)),
    )
    scalars = jnp.stack([start_block, base_block]).astype(jnp.int32)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(into.shape, into.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(scalars, vals, into)


# ---------------------------------------------------------------------------
# fused dense Rand-k reconstruction (simulator hot path)
# ---------------------------------------------------------------------------

MASK_LANES = 128
_MASK_ROWS = 512  # (512, 128) f32 block = 256 KiB VMEM per input


def _mask_kernel(starts_ref, x_ref, o_ref, *, d: int, k: int, lanes: int,
                 block_rows: int):
    m = pl.program_id(0)
    j = pl.program_id(1)
    start = starts_ref[m]
    base = j * block_rows * lanes
    row_i = jax.lax.broadcasted_iota(jnp.int32, (1, block_rows, lanes), 1)
    lane_i = jax.lax.broadcasted_iota(jnp.int32, (1, block_rows, lanes), 2)
    idx = base + row_i * lanes + lane_i  # flat coordinate within this client
    # circular window of k real coordinates: (idx - start) mod d < k; padding
    # coordinates (idx >= d) are always dropped. `idx - start + d` keeps the
    # rem argument non-negative (lax.rem keeps the dividend's sign).
    off = jax.lax.rem(idx - start + d, d)
    inside = (off < k) & (idx < d)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.where(inside, x * (d / k), 0.0).astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("d", "k", "block_rows", "interpret"))
def randk_mask(x: jax.Array, starts: jax.Array, *, d: int, k: int,
               block_rows: int = _MASK_ROWS,
               interpret: bool | None = None) -> jax.Array:
    """Dense circular-window Rand-k for M clients in one launch.

    x: (M, Dp) with Dp % (block_rows*MASK_LANES) adjusted internally;
    starts: (M,) int32 window offsets in [0, d). `d` is the REAL flat length
    (d <= Dp); coordinates past d are padding and stay zero. Returns Q(x)
    with Q(x)[m, i] = x[m, i] * (d/k) if (i - starts[m]) mod d < k else 0.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, dp = x.shape
    rows = dp // MASK_LANES
    if interpret:
        br = rows  # one grid step per client (see kernels/qsgd.py note)
    else:
        br = min(block_rows, rows)
        while rows % br:  # keep the grid exact (dp is 1024-aligned by callers)
            br //= 2
        br = max(br, 1)
    grid = (m, rows // br)
    xt = x.reshape(m, rows, MASK_LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((1, br, MASK_LANES), lambda i, j, starts: (i, j, 0))],
        out_specs=pl.BlockSpec((1, br, MASK_LANES), lambda i, j, starts: (i, j, 0)),
    )
    out = pl.pallas_call(
        partial(_mask_kernel, d=d, k=k, lanes=MASK_LANES, block_rows=br),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, rows, MASK_LANES), x.dtype),
        interpret=interpret,
    )(starts.astype(jnp.int32), xt)
    return out.reshape(m, dp)
