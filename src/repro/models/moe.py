"""Mixture-of-Experts FFN — capacity-free (dropless) top-k routing.

TPU-native dispatch (DESIGN.md §5): tokens stay data-sharded, every expert's
d_ff is tensor-parallel over the "model" axis, and dispatch is a *per-batch-row
local sort* + `lax.ragged_dot_general`:

  1. router logits -> top-k experts + softmax weights per token
  2. per batch row, replicate tokens k times and argsort by expert id
     (a local sort: the sorted axis is never sharded, so no collectives)
  3. one batched ragged_dot per FFN matmul — only active-expert FLOPs
  4. unsort, weighted-sum over the k copies

Qwen2-MoE's 4 shared experts are folded into one dense FFN of width
`shared_expert_ff` applied to every token (mathematically identical to always-
routed experts of the same total width).

Note (roofline): on the CPU backend XLA lowers ragged_dot as a dense
group-loop, so `cost_analysis()` FLOPs over-count by ~E/k; on TPU the
Megablox/grouped-matmul lowering does active FLOPs only. Recorded in
EXPERIMENTS.md §Roofline via the MODEL_FLOPS/HLO_FLOPS ratio.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import custom_batching, lax
from jax.lax import RaggedDotDimensionNumbers, ragged_dot_general

from repro.models.config import ArchConfig
from repro.models.layers import init_mlp, linear, mlp


def init_moe(key, cfg: ArchConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, f), cfg.dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), cfg.dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), cfg.dtype) * s_out,
    }
    if cfg.shared_expert_ff:
        p["shared"] = init_mlp(ks[4], d, cfg.shared_expert_ff, cfg.act, cfg.dtype)
    return p


_RAGGED_DN = RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((2,), (1,)), ((), ())),
    lhs_ragged_dimensions=[1],
    rhs_group_dimensions=[0],
)


def _ragged_dot(lhs, rhs, group_sizes):
    return ragged_dot_general(lhs, rhs, group_sizes, _RAGGED_DN,
                              preferred_element_type=lhs.dtype)


def _batch_all(axis_size, in_batched, *xs):
    """Give every operand a leading batch axis (broadcast where absent)."""
    return [x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, b in zip(xs, in_batched)]


def _fold_groups(a, lhs, group_sizes):
    """(A, B, T, ...) tokens + (A, B, E) sizes -> (B, A*T, ...) + (B, A*E):
    each row's tokens of the A batch members one after another, so the
    groups run (member, expert) in order."""
    b, t = lhs.shape[1], lhs.shape[2]
    lhs = jnp.moveaxis(lhs, 0, 1).reshape((b, a * t) + lhs.shape[3:])
    gs = jnp.moveaxis(group_sizes, 0, 1).reshape(b, -1)
    return lhs, gs


# Grouped matmul with a batching rule of its own. Under the train step's
# per-client vmap the experts' weights are batched too, and JAX cannot batch
# ragged_dot_general; the rule folds the client axis into the token axis
# (each client's experts become groups of their own), so the batched call is
# one ragged dot again. The backward pass is written with the same two
# primitives, so it batches the same way.
@custom_batching.custom_vmap
def _grouped(lhs, rhs, group_sizes):
    """lhs (B, T, K) tokens sorted by group per row, rhs (E, K, N),
    group_sizes (B, E) -> (B, T, N)."""
    return _ragged_dot(lhs, rhs, group_sizes)


@_grouped.def_vmap
def _grouped_vmap(axis_size, in_batched, lhs, rhs, group_sizes):
    if not in_batched[1]:  # shared weights: the batch members are more rows
        lhs, gs = _batch_all(axis_size, (in_batched[0], in_batched[2]),
                             lhs, group_sizes)
        out = _grouped(lhs.reshape((-1,) + lhs.shape[2:]), rhs,
                       gs.reshape((-1,) + gs.shape[2:]))
        return out.reshape((axis_size, -1) + out.shape[1:]), True
    lhs, rhs, gs = _batch_all(axis_size, in_batched, lhs, rhs, group_sizes)
    b, t = lhs.shape[1], lhs.shape[2]
    lhs, gs = _fold_groups(axis_size, lhs, gs)
    out = _grouped(lhs, rhs.reshape((-1,) + rhs.shape[2:]), gs)
    return jnp.moveaxis(out.reshape(b, axis_size, t, -1), 1, 0), True


@custom_batching.custom_vmap
def _grouped_wgrad(lhs, g, group_sizes):
    """Weight cotangent of `_grouped`: (E, K, N) = per group, the sum over
    its tokens of lhs^T g."""
    e = group_sizes.shape[-1]
    rhs = jnp.zeros((e, lhs.shape[-1], g.shape[-1]), lhs.dtype)
    _, vjp = jax.vjp(lambda r: _ragged_dot(lhs, r, group_sizes), rhs)
    return vjp(g)[0]


@_grouped_wgrad.def_vmap
def _grouped_wgrad_vmap(axis_size, in_batched, lhs, g, group_sizes):
    lhs, g, gs = _batch_all(axis_size, in_batched, lhs, g, group_sizes)
    e = gs.shape[-1]
    lhs_f, gs_f = _fold_groups(axis_size, lhs, gs)
    g_f, _ = _fold_groups(axis_size, g, gs)
    out = _grouped_wgrad(lhs_f, g_f, gs_f)
    return out.reshape((axis_size, e) + out.shape[1:]), True


@jax.custom_vjp
def _ragged(lhs, rhs, group_sizes):
    """lhs (B, T, K_dim) x rhs (E, K_dim, N) grouped by row -> (B, T, N)."""
    return _grouped(lhs, rhs, group_sizes)


def _ragged_fwd(lhs, rhs, group_sizes):
    return _grouped(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _ragged_bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _grouped(g, jnp.swapaxes(rhs, 1, 2), group_sizes)
    d_rhs = _grouped_wgrad(lhs, g, group_sizes)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_ragged.defvjp(_ragged_fwd, _ragged_bwd)


def moe_ffn(p, x, cfg: ArchConfig, *, return_aux: bool = False):
    """x: (B, S, D) -> (B, S, D). Works for S == 1 (decode) unchanged."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = linear(x.astype(jnp.float32), p["router"])  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, k)  # (B, S, K)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

    # flatten token copies per row: (B, S*K)
    flat_e = top_e.reshape(b, s * k)
    order = jnp.argsort(flat_e, axis=-1)  # local sort per batch row
    inv = jnp.argsort(order, axis=-1)
    xk = jnp.repeat(x, k, axis=1)  # (B, S*K, D) token copies
    xs = jnp.take_along_axis(xk, order[..., None], axis=1)
    counts = jnp.sum(
        jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=1
    )  # (B, E) group sizes

    if cfg.act == "swiglu":
        h = jax.nn.silu(_ragged(xs, p["w_gate"], counts)) * _ragged(
            xs, p["w_up"], counts
        )
    else:
        h = jax.nn.gelu(_ragged(xs, p["w_up"], counts))
    ys = _ragged(h, p["w_down"], counts)  # (B, S*K, D)

    yk = jnp.take_along_axis(ys, inv[..., None], axis=1).reshape(b, s, k, d)
    y = jnp.sum(yk * top_w[..., None].astype(yk.dtype), axis=2)

    if cfg.shared_expert_ff:
        y = y + mlp(x, p["shared"], cfg.act)

    if return_aux:
        # Switch-style load-balance diagnostics (fraction routed per expert
        # vs mean router prob) — exposed to the training loop for logging.
        frac = jnp.mean(
            jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=(0, 1, 2)
        )
        mean_p = jnp.mean(probs, axis=(0, 1))
        aux = e * jnp.sum(frac * mean_p)
        return y, aux
    return y


def moe_ffn_ref(p, x, cfg: ArchConfig):
    """Dense-einsum oracle (all experts for all tokens, masked sum) — used by
    tests to validate the ragged dispatch."""
    e, k = cfg.num_experts, cfg.experts_per_token
    logits = linear(x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    # (B, S, E) combine weights
    comb = jnp.zeros(probs.shape, jnp.float32)
    comb = jnp.sum(jax.nn.one_hot(top_e, e) * top_w[..., None], axis=2)
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"])) * jnp.einsum(
            "bsd,edf->bsef", x, p["w_up"]
        )
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,edf->bsef", x, p["w_up"]))
    y_all = jnp.einsum("bsef,efd->bsed", h, p["w_down"])
    y = jnp.sum(y_all * comb[..., None].astype(y_all.dtype), axis=2)
    if cfg.shared_expert_ff:
        y = y + mlp(x, p["shared"], cfg.act)
    return y
