"""Pallas TPU kernels: bit-packed wire slabs (quantize/pack/unpack-reduce).

The shared wire's Rand-block slab is an f32 (K, D) buffer; moving it at four
bytes per lane wastes the interconnect the paper's communication-complexity
curves are about. These kernels make the slab's *wire* representation a byte
lattice (DESIGN.md §3.13):

  pack_slab       (K, D) f32 values + uniforms -> (packed uint8, scales)
                  Per-row max-abs scale, stochastic rounding to integer
                  levels q in [-L, L], biased to the byte b = q + L. With
                  ``nibble=True`` two consecutive ROWS share a byte
                  (lo | hi<<4): K is BLOCK_ROWS-aligned (even) on the wire,
                  and pairing rows instead of lanes keeps the lane dimension
                  D intact for TPU tiling. Scales stay an f32 (K, 1)
                  sideband: scale_r = (maxabs_r + eps) / L.
  unpack_slab     decode one packed slab back to f32: v = (b - L) * scale.
                  This is the ONLY dequantization formula in the repo — the
                  f32-transport quantized wire round-trips through the same
                  pack/unpack pair, which is what makes packed8 transport
                  bit-match the f32 wire (same byte, same scale, same
                  multiply).
  unpack_reduce   the fused unpack-accumulate half of the packed collective:
                  all-gathered (R, Kp, D) bytes + (R, K, 1) scales -> the
                  f32 mean slab in ONE kernel — grid (row blocks, R) with
                  the rank innermost: each step decodes rank r's row block
                  and accumulates into the resident output block, the last
                  rank divides by R. Accumulation is in rank
                  order, which bit-matches ``lax.pmean`` of the decoded
                  slabs on the meshes we run (R a power of two; the division
                  by R is then exact either way).

Bias representation needs 2L+1 <= 256 byte values (L <= 127 for int8,
L <= 7 for the nibble lanes); `core.dist` validates the caps. The uniforms
are generated OUTSIDE the kernel (shared wire key + WIRE_QUANT_SALT) and
streamed in, like kernels/qsgd.py.

Blocking (`_row_block`): every kernel tiles the slab's rows, so its VMEM use
is bounded by the row block and the lane width D, never by K or the rank
count R. A packed uint8 block holds a multiple of 32 rows (the uint8 tile is
(32, 128)); for nibble slabs that is 64 value rows per block, so every block
holds whole row pairs. Rows are padded up to a whole number of blocks inside
each wrapper and the padding is sliced off again: the shapes callers see
(and the bytes the wire moves) do not depend on the blocking.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.randk import BLOCK_ROWS


def _quantize(x, u, levels: int):
    """f32 block -> (biased int32 lattice, f32 per-row scale)."""
    s = float(levels)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True) + 1e-30
    y = jnp.abs(x) / amax * s  # in [0, s]
    f = jnp.floor(y)
    q = jnp.minimum(f + (u < (y - f)).astype(jnp.float32), s)
    b = (jnp.sign(x) * q + s).astype(jnp.int32)  # biased, in [0, 2s]
    return b, amax / s


def _pair_rows(b):
    """(rows, D) int32 lattice -> (rows/2, D) two-nibble bytes (lo | hi<<4)."""
    rows, d = b.shape
    br = b.reshape(rows // 2, 2, d)
    return br[:, 0, :] + 16 * br[:, 1, :]


def _decode(p, scales, levels: int, nibble: bool):
    """Packed uint8 block + (rows, 1) scales -> f32 values (b - L) * scale."""
    b = p.astype(jnp.int32)
    if nibble:
        prows, d = b.shape
        lo = jax.lax.rem(b, 16)
        hi = b // 16
        b = jnp.stack([lo, hi], axis=1).reshape(prows * 2, d)
    return (b.astype(jnp.float32) - float(levels)) * scales


def _pack_kernel(x_ref, u_ref, p_ref, s_ref, *, levels: int, nibble: bool):
    b, scale = _quantize(x_ref[...].astype(jnp.float32), u_ref[...], levels)
    if nibble:
        b = _pair_rows(b)
    p_ref[...] = b.astype(jnp.uint8)
    s_ref[...] = scale.astype(jnp.float32)


def _unpack_kernel(p_ref, s_ref, o_ref, *, levels: int, nibble: bool):
    o_ref[...] = _decode(p_ref[...], s_ref[...], levels, nibble)


def _unpack_reduce_kernel(p_ref, s_ref, o_ref, *, levels: int, nibble: bool,
                          ranks: int):
    r = pl.program_id(1)
    contrib = _decode(p_ref[0], s_ref[0], levels, nibble)

    @pl.when(r == 0)
    def _():
        o_ref[...] = contrib

    @pl.when(r != 0)
    def _():
        o_ref[...] = o_ref[...] + contrib

    @pl.when(r == ranks - 1)
    def _():
        o_ref[...] = o_ref[...] / float(ranks)


# bytes of one f32 (rows, D) operand block; with double buffering and the
# kernels' f32 temporaries a grid step stays well inside the scoped VMEM
_BLOCK_BYTES = 1 << 20


def _row_block(kp: int, d: int, nibble: bool) -> int:
    """Value rows per grid step: whole packed uint8 tiles of 32 rows (64
    value rows when two rows share a byte), as many as fit _BLOCK_BYTES at
    lane width d, and no more than the slab needs."""
    align = 64 if nibble else 32
    rows = max(align, (_BLOCK_BYTES // (4 * d)) // align * align)
    return min(rows, -(-kp // align) * align)


def _pad_axis(x, to: int, axis: int):
    pad = to - x.shape[axis]
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


@partial(jax.jit, static_argnames=("levels", "nibble", "interpret"))
def pack_slab(vals: jax.Array, u: jax.Array, *, levels: int,
              nibble: bool = False, interpret: bool | None = None):
    """vals, u: (K, D). Returns (packed uint8, scales (Kp, 1) f32) with
    Kp = K padded to a BLOCK_ROWS multiple; packed is (Kp, D) or, with
    nibble=True, (Kp/2, D). Padding rows quantize to the zero byte."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    k, d = vals.shape
    kp = k + (-k) % BLOCK_ROWS
    rows = _row_block(kp, d, nibble)
    kt = -(-kp // rows) * rows
    vals = _pad_axis(vals.astype(jnp.float32), kt, 0)
    u = _pad_axis(u, kt, 0)
    prows = rows // 2 if nibble else rows
    packed, scales = pl.pallas_call(
        partial(_pack_kernel, levels=levels, nibble=nibble),
        grid=(kt // rows,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((prows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((kt // 2 if nibble else kt, d), jnp.uint8),
            jax.ShapeDtypeStruct((kt, 1), jnp.float32),
        ),
        interpret=interpret,
    )(vals, u)
    return packed[:kp // 2 if nibble else kp], scales[:kp]


@partial(jax.jit, static_argnames=("levels", "n_rows", "nibble", "interpret"))
def unpack_slab(packed: jax.Array, scales: jax.Array, *, levels: int,
                n_rows: int, nibble: bool = False,
                interpret: bool | None = None) -> jax.Array:
    """(Kp[/2], D) packed + (Kp, 1) scales -> (n_rows, D) f32 values."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    kp = scales.shape[0]
    d = packed.shape[1]
    rows = _row_block(kp, d, nibble)
    kt = -(-kp // rows) * rows
    prows = rows // 2 if nibble else rows
    out = pl.pallas_call(
        partial(_unpack_kernel, levels=levels, nibble=nibble),
        grid=(kt // rows,),
        in_specs=[
            pl.BlockSpec((prows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((kt, d), jnp.float32),
        interpret=interpret,
    )(_pad_axis(packed, kt // 2 if nibble else kt, 0),
      _pad_axis(scales, kt, 0))
    return out[:n_rows]


@partial(jax.jit, static_argnames=("levels", "n_rows", "nibble", "interpret"))
def unpack_reduce(packed: jax.Array, scales: jax.Array, *, levels: int,
                  n_rows: int, nibble: bool = False,
                  interpret: bool | None = None) -> jax.Array:
    """All-gathered (R, Kp[/2], D) packed + (R, Kp, 1) scales -> the
    (n_rows, D) f32 MEAN slab, decoded and accumulated in rank order in one
    kernel (the receive half of the packed collective)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    r, _, d = packed.shape
    kp = scales.shape[1]
    rows = _row_block(kp, d, nibble)
    kt = -(-kp // rows) * rows
    prows = rows // 2 if nibble else rows
    out = pl.pallas_call(
        partial(_unpack_reduce_kernel, levels=levels, nibble=nibble, ranks=r),
        grid=(kt // rows, r),
        in_specs=[
            pl.BlockSpec((1, prows, d), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, rows, 1), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((kt, d), jnp.float32),
        interpret=interpret,
    )(_pad_axis(packed, kt // 2 if nibble else kt, 1),
      _pad_axis(scales, kt, 1))
    return out[:n_rows]
