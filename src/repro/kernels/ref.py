"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

All functions operate on the same padded/tiled views the kernels see, so
tests compare bit-for-bit semantics (modulo float accumulation order).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def qsgd_quantize_ref(x: jax.Array, u: jax.Array, *, levels: int,
                      tile: int = 1024) -> jax.Array:
    """Blockwise stochastic quantization (TPU-native QSGD variant).

    x: (N,) f32 with N % tile == 0; u: (N,) uniform [0,1) randoms.
    Each `tile` block is scaled by its own max-abs (the lane-aligned
    per-block scale that replaces QSGD's global L2 norm on TPU; unbiased
    conditional on the block scale).
    """
    xt = x.reshape(-1, tile).astype(jnp.float32)
    ut = u.reshape(-1, tile)
    scale = jnp.max(jnp.abs(xt), axis=1, keepdims=True) + 1e-30
    s = float(levels)
    y = jnp.abs(xt) / scale * s
    f = jnp.floor(y)
    q = f + (ut < (y - f)).astype(jnp.float32)
    out = jnp.sign(xt) * q * (scale / s)
    return out.reshape(x.shape).astype(x.dtype)


def randk_compress_ref(rows: jax.Array, start_block: jax.Array, *,
                       k_blocks: int, block_rows: int) -> jax.Array:
    """Circular block-aligned row gather + unbiased (n/k) scaling.

    rows: (N, D) with N % block_rows == 0. Returns (k_blocks*block_rows, D).
    """
    n, d = rows.shape
    nb = n // block_rows
    blocks = rows.reshape(nb, block_rows, d)
    idx = (start_block + jnp.arange(k_blocks)) % nb
    vals = blocks[idx].reshape(k_blocks * block_rows, d)
    return vals * (nb / k_blocks)


def randk_decompress_ref(vals: jax.Array, start_block: jax.Array, *,
                         n_rows: int, block_rows: int) -> jax.Array:
    """Scatter the compressed row-block back into an (N, D) zero canvas."""
    k, d = vals.shape
    kb = k // block_rows
    nb = n_rows // block_rows
    canvas = jnp.zeros((nb, block_rows, d), vals.dtype)
    idx = (start_block + jnp.arange(kb)) % nb
    canvas = canvas.at[idx].set(vals.reshape(kb, block_rows, d))
    return canvas.reshape(n_rows, d)


def randk_decompress_into_ref(into: jax.Array, vals: jax.Array,
                              start_block: jax.Array, base_block: jax.Array,
                              *, n_rows: int, block_rows: int) -> jax.Array:
    """Overwrite the circular window of the n_rows-row segment that starts
    at block `base_block` of `into` (R, D) with `vals` (K, D)."""
    k, d = vals.shape
    kb = k // block_rows
    nb = n_rows // block_rows
    blocks = into.reshape(-1, block_rows, d)
    idx = base_block + (start_block + jnp.arange(kb)) % nb
    blocks = blocks.at[idx].set(vals.reshape(kb, block_rows, d).astype(
        into.dtype))
    return blocks.reshape(into.shape)


def randk_mask_ref(x: jax.Array, starts: jax.Array, *, d: int, k: int) -> jax.Array:
    """Dense circular-window Rand-k, batched over clients.

    x: (M, Dp) possibly padded past the real flat length d; starts: (M,).
    Q(x)[m, i] = x[m, i] * (d/k) for (i - starts[m]) mod d < k, else 0.
    """
    dp = x.shape[1]
    idx = jnp.arange(dp, dtype=jnp.int32)
    off = jnp.mod(idx[None, :] - starts[:, None].astype(jnp.int32), d)
    inside = (off < k) & (idx[None, :] < d)
    return jnp.where(inside, x.astype(jnp.float32) * (d / k), 0.0).astype(x.dtype)


def _pad_rows_ref(x, block_rows: int):
    pad = (-x.shape[0]) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


def pack_slab_ref(vals: jax.Array, u: jax.Array, *, levels: int,
                  nibble: bool = False, block_rows: int = 8):
    """Quantize + bit-pack one wire slab (oracle for kernels/pack.py).

    vals, u: (K, D); rows pad to a `block_rows` multiple. Per-row max-abs
    scale, stochastic rounding to q in [-levels, levels], biased byte
    b = q + levels. nibble=True packs two consecutive ROWS per byte
    (lo | hi<<4). Returns (packed uint8, scales (Kp, 1) f32)."""
    x = _pad_rows_ref(vals.astype(jnp.float32), block_rows)
    ut = _pad_rows_ref(u, block_rows)
    s = float(levels)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True) + 1e-30
    y = jnp.abs(x) / amax * s
    f = jnp.floor(y)
    q = jnp.minimum(f + (ut < (y - f)).astype(jnp.float32), s)
    b = (jnp.sign(x) * q + s).astype(jnp.int32)
    if nibble:
        kp, d = b.shape
        br = b.reshape(kp // 2, 2, d)
        b = br[:, 0, :] + 16 * br[:, 1, :]
    return b.astype(jnp.uint8), (amax / s).astype(jnp.float32)


def _decode_ref(packed: jax.Array, scales: jax.Array, levels: int,
                nibble: bool) -> jax.Array:
    b = packed.astype(jnp.int32)
    if nibble:
        prows, d = b.shape
        b = jnp.stack([b % 16, b // 16], axis=1).reshape(prows * 2, d)
    return (b.astype(jnp.float32) - float(levels)) * scales


def unpack_slab_ref(packed: jax.Array, scales: jax.Array, *, levels: int,
                    n_rows: int, nibble: bool = False) -> jax.Array:
    """Decode one packed slab: v = (b - levels) * scale, trimmed to n_rows."""
    return _decode_ref(packed, scales, levels, nibble)[:n_rows]


def unpack_reduce_ref(packed: jax.Array, scales: jax.Array, *, levels: int,
                      n_rows: int, nibble: bool = False) -> jax.Array:
    """(R, Kp[/2], D) packed + (R, Kp, 1) scales -> (n_rows, D) mean slab.

    Accumulates decoded slabs in RANK ORDER (r = 0..R-1) then divides by R —
    the exact float schedule of the fused kernel, which in turn bit-matches
    `lax.pmean` of the decoded slabs on power-of-two rank counts."""
    r = packed.shape[0]
    acc = _decode_ref(packed[0], scales[0], levels, nibble)
    for i in range(1, r):
        acc = acc + _decode_ref(packed[i], scales[i], levels, nibble)
    return (acc / float(r))[:n_rows]


def diana_shift_update_ref(h, q_own, mh, q_mean, alpha: float,
                           beta: float | None = None):
    """Fused DIANA state update (Algorithm 3/5 lines 7-11):
        direction = H_t + Q_mean
        h'        = h  + alpha * Q_own
        H'        = H_t + beta  * Q_mean
    `beta` defaults to alpha; under cohort sampling the caller passes
    beta = (M/C)*alpha so H tracks the population mean shift.
    Returns (direction, h', H'). All f32 math, cast back to input dtypes.
    """
    f = jnp.float32
    if beta is None:
        beta = alpha
    direction = mh.astype(f) + q_mean.astype(f)
    h_new = h.astype(f) + alpha * q_own.astype(f)
    mh_new = mh.astype(f) + beta * q_mean.astype(f)
    return (direction.astype(q_mean.dtype), h_new.astype(h.dtype),
            mh_new.astype(mh.dtype))
