"""Sharding rules: param/state/batch PartitionSpecs for the production mesh.

Layout (DESIGN.md §5) — Megatron-style TP over "model", clients over
("pod","data"):

  embeddings / lm_head (V, D)           -> ("model", None)   vocab-parallel
  column-parallel projections (.., D,F) -> last axis "model"
  row-parallel projections    (.., F,D) -> axis -2  "model"
  per-head vectors (.., H, hd)          -> axis -2, falling back to the last
                                           axis when H doesn't divide the
                                           model axis (hymba's 25 heads)
  norms / router / small vectors        -> replicated
  MLA: latent projection wkv_a          -> replicated; wkv_b column-parallel
  DIANA shifts (M, *param)              -> ("pod","data") on axis 0 + param spec
  batches                               -> axis 0 over ("pod","data")

jit argument shardings must divide exactly (GSPMD pads intermediates, not
arguments), so every rule checks divisibility and falls back to the next
candidate axis, then to replication. Weight leaves carry a leading
stacked-layer axis — never sharded (it's the `lax.scan` axis).
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# last-axis column-parallel weights (and their biases); wkv_b expands the
# latent into the heads' keys and values
_COL = {
    "wq", "wk", "wv", "wx", "wbc", "wdt", "wr", "wg", "w_up", "w_gate", "wA",
    "bq", "bk", "bv", "b_up", "w0", "mu", "wkv_b",
}
# axis -2 row-parallel weights / per-head (H, hd) tensors
_ROW = {"wo", "w_down", "wo_fused", "wB", "u", "ln", "ln_attn", "ln_out"}
_VOCAB = {"embed", "lm_head"}
# wkv_a projects to the latent every head reads; b_router is the router's
# selection bias
_REPLICATED = {"router", "b_router", "scale", "bias", "a_log", "pos_embed",
               "wkv_a"}


def _model_size(mesh) -> int:
    return int(mesh.shape["model"]) if mesh is not None else 16


def _path_names(path) -> list[str]:
    out = []
    for pk in path:
        if isinstance(pk, jax.tree_util.DictKey):
            out.append(str(pk.key))
        elif isinstance(pk, jax.tree_util.GetAttrKey):
            out.append(pk.name)
    return out


def _leaf_spec(path, leaf, msize: int) -> P:
    names = _path_names(path)
    name = names[-1] if names else ""
    nd = leaf.ndim
    shape = leaf.shape

    def try_axes(*axes):
        entries = [None] * nd
        for ax in axes:
            if 0 <= ax < nd and shape[ax] % msize == 0 and shape[ax] > 0:
                entries[ax] = "model"
                return P(*entries)
        return P(*entries)

    if name in _VOCAB:
        return try_axes(0)
    if name in _REPLICATED:
        return P(*(None,) * nd)
    if name in _COL and nd >= 1:
        return try_axes(nd - 1)
    if name in _ROW and nd >= 2:
        return try_axes(nd - 2, nd - 1)
    return P(*(None,) * nd)


def param_specs(params, *, mesh=None) -> Any:
    """PartitionSpec pytree matching `params` (abstract or concrete)."""
    msize = _model_size(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _leaf_spec(p, l, msize), params)


def param_shardings(mesh, params) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh=mesh))


def shifts_specs(params, client_axes: tuple[str, ...], *, mesh=None,
                 n_slots: int = 0) -> Any:
    """DIANA per-client shifts: leading client axis over ('pod','data').

    n_slots >= 1 (DIANA-RR slot tables, leaves (M, n_slots, *param))
    inserts a replicated slot axis between the client axis and the param
    spec — the axis is present whenever the RULE is slotted, size-1 tables
    included; 0 means no slot axis (non-slotted rules)."""
    msize = _model_size(mesh)
    slot = (None,) if n_slots else ()

    def shift_spec(path, leaf):
        base = _leaf_spec(path, leaf, msize)
        return P(client_axes, *slot, *base)

    return jax.tree_util.tree_map_with_path(shift_spec, params)


def podded_specs(params, pod_axes: tuple[str, ...], *, mesh=None,
                 n_slots: int = 0) -> Any:
    """Per-pod state (level-2 DIANA shifts, per-pod mean shifts, local NASTYA
    params): leading pod axis + the leaf's own TP spec (replicated slot axis
    inserted when n_slots >= 1; 0 = no slot axis)."""
    msize = _model_size(mesh)
    slot = (None,) if n_slots else ()

    def spec(path, leaf):
        base = _leaf_spec(path, leaf, msize)
        return P(pod_axes, *slot, *base)

    return jax.tree_util.tree_map_with_path(spec, params)


def slotted_specs(params, *, mesh=None, n_slots: int = 0) -> Any:
    """Param-aligned specs with a leading replicated slot axis (flat-mesh
    DIANA-RR mean tables, global pod_mean_shift): leaves (n_slots, *param);
    n_slots=0 degrades to plain param specs."""
    msize = _model_size(mesh)
    slot = (None,) if n_slots else ()
    return jax.tree_util.tree_map_with_path(
        lambda p, l: P(*slot, *_leaf_spec(p, l, msize)), params)


def batch_specs(batch, client_axes: tuple[str, ...]) -> Any:
    return jax.tree.map(lambda x: P(client_axes, *(None,) * (x.ndim - 1)), batch)


def cache_specs(cache, client_axes: tuple[str, ...], *, mesh,
                n_clients: int = 1) -> Any:
    """Decode-cache shardings. Cache leaves are (L, B, ...):

    - B >= n_clients (and divisible): batch over client axes; then the
      widest divisible remaining axis over "model".
    - B  < n_clients (long_500k, B=1): batch replicated; the widest axis
      over ("data","model") jointly when divisible, else "model"-only,
      else replicated.
    """
    msize = _model_size(mesh)
    joint = int(np.prod([mesh.shape[a] for a in (*client_axes, "model")]))

    def spec(leaf):
        nd = leaf.ndim
        if nd < 2:
            return P(*(None,) * nd)
        b = leaf.shape[1]
        entries: list[Any] = [None] * nd
        shard_batch = b >= n_clients and b % n_clients == 0
        rest = sorted(range(2, nd), key=lambda i: -leaf.shape[i])
        if shard_batch:
            entries[1] = client_axes
            for i in rest:
                if leaf.shape[i] % msize == 0:
                    entries[i] = "model"
                    break
        else:
            for i in rest:
                if leaf.shape[i] % joint == 0:
                    entries[i] = (*client_axes, "model")
                    break
                if leaf.shape[i] % msize == 0:
                    entries[i] = "model"
                    break
        return P(*entries)

    return jax.tree.map(spec, cache)


def zero1_specs(params, client_axes: tuple[str, ...], *, mesh=None) -> Any:
    """Optimizer-state sharding: param spec + client axes on the first
    unsharded, divisible axis (ZeRO-1)."""
    msize = _model_size(mesh)
    csize = (int(np.prod([mesh.shape[a] for a in client_axes]))
             if mesh is not None else 16)

    def spec(path, leaf):
        base = list(_leaf_spec(path, leaf, msize))
        start = 1 if "blocks" in _path_names(path) and leaf.ndim >= 2 else 0
        for i in range(start, leaf.ndim):  # never shard the scan (layer) axis
            if base[i] is None and leaf.shape[i] % csize == 0 and leaf.shape[i] > 0:
                base[i] = client_axes if len(client_axes) > 1 else client_axes[0]
                break
        return P(*base)

    return jax.tree_util.tree_map_with_path(spec, params)
