"""Pallas TPU kernel: fused DIANA shift/direction update.

The per-step elementwise hot loop the paper's method adds on top of SGD
(Algorithm 3 lines 7-9 / Algorithm 5 lines 8-11):

    direction = H_t + Q_mean
    h'        = h   + alpha * Q_own
    H'        = H_t + beta  * Q_mean

`beta` defaults to `alpha` (the paper's full-participation form). Under
cohort sampling only M of C clients contribute per round, so the resident
mean shift H tracks (C/M)*h_bar unless the H update is rescaled by M/C —
the second stepsize beta = (M/C)*alpha (DESIGN.md §3.10).

Unfused this is five HBM round-trips over param-sized arrays; the kernel
streams all four inputs once per (block, 128) VMEM tile and writes the three
outputs in the same pass.

It runs where the update is dense: the simulator's `tree_diana_shift`, the
local (NASTYA) family's server `direction`, and 'diana' over the
independent wire. The shared wire's DIANA update does not call it: there
the message is zero outside the Rand-block window, so `core.dist` updates
only the window's rows, in plain jnp, and writes them back in place
(DESIGN.md §3.5).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
_BLOCK = 512  # rows of 128 lanes per grid step -> 256 KiB/input in VMEM


def _shift_kernel(h_ref, qo_ref, mh_ref, qm_ref, dir_ref, h_out, mh_out, *,
                  alpha: float, beta: float):
    h = h_ref[...].astype(jnp.float32)
    qo = qo_ref[...].astype(jnp.float32)
    mh = mh_ref[...].astype(jnp.float32)
    qm = qm_ref[...].astype(jnp.float32)
    dir_ref[...] = (mh + qm).astype(dir_ref.dtype)
    h_out[...] = (h + alpha * qo).astype(h_out.dtype)
    mh_out[...] = (mh + beta * qm).astype(mh_out.dtype)


@partial(jax.jit, static_argnames=("alpha", "beta", "interpret"))
def diana_shift_update(h, q_own, mh, q_mean, *, alpha: float,
                       beta: float | None = None,
                       interpret: bool | None = None):
    """All inputs (N,) with N % LANES == 0. Returns (direction, h', H')."""
    if beta is None:
        beta = alpha
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n = h.shape[0]
    rows = n // LANES
    # single grid step in interpret mode (see kernels/qsgd.py note)
    br = rows if interpret else min(_BLOCK, rows)
    grid = (pl.cdiv(rows, br),)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    view = lambda x: x.reshape(rows, LANES)
    direction, h_new, mh_new = pl.pallas_call(
        partial(_shift_kernel, alpha=alpha, beta=beta),
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), q_mean.dtype),
            jax.ShapeDtypeStruct((rows, LANES), h.dtype),
            jax.ShapeDtypeStruct((rows, LANES), mh.dtype),
        ],
        interpret=interpret,
    )(view(h), view(q_own), view(mh), view(q_mean))
    return direction.reshape(n), h_new.reshape(n), mh_new.reshape(n)
