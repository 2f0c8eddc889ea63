"""Model assembly: embeddings + scan-over-layers blocks + LM head.

One assembly covers all six assigned families (DESIGN.md §4):

  dense  — GQA + RoPE (+ optional sliding window / QKV bias)
  moe    — dense or latent attention + capacity-free top-k MoE FFN
           (`moe.py`), optionally after leading dense layers
  ssm    — RWKV6 mixer, attention-free (`mixers.py`)
  hybrid — Hymba parallel attention+SSD heads
  vlm    — qwen2-vl: M-RoPE, patch-embedding stub spliced into the stream
  audio  — whisper: bidirectional encoder over frame-embedding stub +
           causal decoder with cross-attention

Layer parameters are *stacked* (leading L axis) and the stack is traversed
with `lax.scan`, keeping compile time flat in depth (deepseek-67b has 95
layers). Leading dense layers (`first_dense_layers`, DeepSeek's
first_k_dense_replace) are a stack of their own, `dense_blocks`, scanned
before `blocks`; their caches sit under the same two names. Entry points:

  init_params(key, cfg)                         -> params
  loss_fn(params, batch, cfg)                   -> scalar loss
                                                   (, MoE stats)
  forward(params, batch, cfg)                   -> logits          (no loss)
  prefill(params, batch, cfg, cache_len)        -> (last logits, cache)
  decode_step(params, cache, tokens, pos, cfg)  -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import mixers
from repro.models.config import ArchConfig
from repro.models.layers import (
    cross_entropy,
    embed_tokens,
    init_mlp,
    init_norm,
    lm_logits,
    mlp,
    norm,
)
from repro.models.moe import init_moe, moe_ffn


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _dense_config(cfg: ArchConfig) -> ArchConfig:
    """The leading dense layers' config: an MLP of width dense_d_ff."""
    return dataclasses.replace(cfg, num_experts=0, router_experts=0,
                               d_ff=cfg.dense_d_ff, first_dense_layers=0,
                               dense_d_ff=0)


def _stacks(params, cfg: ArchConfig):
    """(name, stacked params, config) of each layer stack, in order."""
    out = []
    if cfg.first_dense_layers:
        out.append(("dense_blocks", params["dense_blocks"],
                    _dense_config(cfg)))
    return out + [("blocks", params["blocks"], cfg)]


def _init_block(key, cfg: ArchConfig):
    ks = jax.random.split(key, 6)
    p = {"ln1": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
         "ln2": init_norm(cfg.d_model, cfg.norm, cfg.dtype)}
    if cfg.attention_mixer == "attn":
        p["mixer"] = mixers.init_attention(ks[0], cfg)
    elif cfg.attention_mixer == "mla":
        p["mixer"] = mixers.init_mla(ks[0], cfg)
    elif cfg.attention_mixer == "rwkv6":
        p["mixer"] = mixers.init_rwkv6(ks[0], cfg)
    elif cfg.attention_mixer == "hymba":
        p["mixer"] = mixers.init_hymba(ks[0], cfg)
    else:
        raise ValueError(cfg.attention_mixer)
    if cfg.num_experts:
        p["ffn"] = init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, cfg.dtype)
    if cfg.is_encdec:
        p["ln_cross"] = init_norm(cfg.d_model, cfg.norm, cfg.dtype)
        p["cross"] = mixers.init_cross_attention(ks[2], cfg)
    return p


def _init_encoder_block(key, cfg: ArchConfig):
    ks = jax.random.split(key, 2)
    return {
        "ln1": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
        "mixer": mixers.init_attention(ks[0], cfg),
        "ln2": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
        "ffn": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, cfg.dtype),
    }


def init_params(key, cfg: ArchConfig):
    ks = jax.random.split(key, 6)
    vp = cfg.padded_vocab()
    p: dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (vp, cfg.d_model), cfg.dtype) * 0.02,
        "blocks": jax.vmap(lambda k: _init_block(k, cfg))(
            jax.random.split(ks[1], cfg.num_layers - cfg.first_dense_layers)
        ),
        "final_norm": init_norm(cfg.d_model, cfg.norm, cfg.dtype),
    }
    if cfg.first_dense_layers:
        p["dense_blocks"] = jax.vmap(
            lambda k: _init_block(k, _dense_config(cfg)))(
                jax.random.split(ks[5], cfg.first_dense_layers))
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(ks[2], (vp, cfg.d_model), cfg.dtype) * 0.02
    if cfg.is_encdec:
        p["enc_blocks"] = jax.vmap(lambda k: _init_encoder_block(k, cfg))(
            jax.random.split(ks[3], cfg.encoder_layers)
        )
        p["enc_final_norm"] = init_norm(cfg.d_model, cfg.norm, cfg.dtype)
        # whisper: learned decoder positions, sinusoidal encoder positions
        p["pos_embed"] = (
            jax.random.normal(ks[4], (cfg.max_seq, cfg.d_model), cfg.dtype) * 0.02
        )
    return p


# ---------------------------------------------------------------------------
# positions (RoPE streams; M-RoPE for the VLM)
# ---------------------------------------------------------------------------

def mrope_grid(cfg: ArchConfig) -> int:
    return max(1, int(math.ceil(math.sqrt(max(cfg.vision_patches, 1)))))


def mrope_positions(cfg: ArchConfig, s: int, b: int):
    """(3, B, S) t/h/w position ids: patch grid then text (qwen2-vl)."""
    g = mrope_grid(cfg)
    i = jnp.arange(s)
    is_patch = i < cfg.vision_patches
    text = g + (i - cfg.vision_patches)
    t = jnp.where(is_patch, 0, text)
    h = jnp.where(is_patch, i // g, text)
    w = jnp.where(is_patch, i % g, text)
    pos = jnp.stack([t, h, w])  # (3, S)
    return jnp.broadcast_to(pos[:, None, :], (3, b, s))


def _positions(cfg: ArchConfig, b: int, s: int, offset: int = 0):
    if cfg.mrope_sections is not None:
        return mrope_positions(cfg, s, b)
    return jnp.broadcast_to(jnp.arange(offset, offset + s), (b, s))


def _decode_rope_positions(cfg: ArchConfig, b: int, pos):
    if cfg.mrope_sections is not None:
        g = mrope_grid(cfg)
        eff = g + (pos - cfg.vision_patches)
        return jnp.broadcast_to(eff, (3, b, 1))
    return jnp.broadcast_to(pos, (b, 1))


def _sinusoid(s: int, d: int, dtype):
    pos = jnp.arange(s)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2)[None].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# block application (train / prefill / decode)
# ---------------------------------------------------------------------------

def _ffn(bp, x, cfg: ArchConfig):
    """(y, stats): MoE routing stats (`moe.moe_routed`), {} for an MLP."""
    if cfg.num_experts:
        return moe_ffn(bp["ffn"], x, cfg, with_stats=True)
    return mlp(x, bp["ffn"], cfg.act), {}


def _block_train(bp, x, cfg: ArchConfig, positions, enc):
    """-> (x, the FFN's stats)."""
    h = norm(x, bp["ln1"], cfg.norm)
    if cfg.attention_mixer == "attn":
        y = mixers.attention_train(bp["mixer"], h, cfg, positions=positions)
    elif cfg.attention_mixer == "mla":
        y = mixers.mla_train(bp["mixer"], h, cfg, positions=positions)
    elif cfg.attention_mixer == "rwkv6":
        y = mixers.rwkv6_train(bp["mixer"], h, cfg)
    else:
        y = mixers.hymba_train(bp["mixer"], h, cfg, positions=positions)
    x = x + y
    if cfg.is_encdec:
        x = x + mixers.cross_attention_train(
            bp["cross"], norm(x, bp["ln_cross"], cfg.norm), enc, cfg
        )
    y, stats = _ffn(bp, norm(x, bp["ln2"], cfg.norm), cfg)
    return x + y, stats


def _block_prefill(bp, x, cfg: ArchConfig, positions, enc, cache_len: int):
    h = norm(x, bp["ln1"], cfg.norm)
    if cfg.attention_mixer == "attn":
        y, c = mixers.attention_prefill(
            bp["mixer"], h, cfg, positions=positions, cache_len=cache_len
        )
    elif cfg.attention_mixer == "mla":
        y, c = mixers.mla_prefill(bp["mixer"], h, cfg, positions=positions,
                                  cache_len=cache_len)
    elif cfg.attention_mixer == "rwkv6":
        y, c = mixers.rwkv6_prefill(bp["mixer"], h, cfg)
    else:
        y, c = mixers.hymba_prefill(
            bp["mixer"], h, cfg, positions=positions, cache_len=cache_len
        )
    x = x + y
    cache = {"mixer": c}
    if cfg.is_encdec:
        hc = norm(x, bp["ln_cross"], cfg.norm)
        x = x + mixers.cross_attention_train(bp["cross"], hc, enc, cfg)
        cache["cross"] = mixers.cross_attention_cache(bp["cross"], enc, cfg)
    return x + _ffn(bp, norm(x, bp["ln2"], cfg.norm), cfg)[0], cache


def _block_decode(bp, x, cfg: ArchConfig, cache, pos, rope_pos):
    h = norm(x, bp["ln1"], cfg.norm)
    if cfg.attention_mixer == "attn":
        y, c = mixers.attention_decode(
            bp["mixer"], h, cfg, cache["mixer"], pos, rope_positions=rope_pos
        )
    elif cfg.attention_mixer == "mla":
        y, c = mixers.mla_decode(bp["mixer"], h, cfg, cache["mixer"], pos)
    elif cfg.attention_mixer == "rwkv6":
        y, c = mixers.rwkv6_decode(bp["mixer"], h, cfg, cache["mixer"])
    else:
        y, c = mixers.hymba_decode(bp["mixer"], h, cfg, cache["mixer"], pos)
    x = x + y
    new_cache = {"mixer": c}
    if cfg.is_encdec:
        hc = norm(x, bp["ln_cross"], cfg.norm)
        x = x + mixers.cross_attention_decode(bp["cross"], hc, cfg, cache["cross"])
        new_cache["cross"] = cache["cross"]
    return x + _ffn(bp, norm(x, bp["ln2"], cfg.norm), cfg)[0], new_cache


def _apply_remat(body, remat):
    """remat: True/"full" = save nothing; "dots" = save matmul outputs with
    no batch dims (weight-stationary recompute only); False/"none" = store
    all activations."""
    if remat is True or remat == "full":
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return body


def _scan_blocks(blocks, x, body, *, remat, unroll: bool = False):
    """Traverse the stacked layer params; body(bp, x) -> (x, aux), and the
    layers' aux come back stacked: -> (x, aux).

    unroll=False: `lax.scan` — flat compile time (the production default).
    unroll=True: python loop — exact per-layer HLO, used by the dry-run so
    `cost_analysis()` / collective parsing see every layer (XLA's cost model
    counts a while-loop body ONCE regardless of trip count; EXPERIMENTS.md
    §Dry-run).
    """
    body = _apply_remat(body, remat)

    if unroll:
        n = jax.tree.leaves(blocks)[0].shape[0]
        auxs = []
        for i in range(n):
            x, aux = body(jax.tree.map(lambda a: a[i], blocks), x)
            auxs.append(aux)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *auxs)

    return lax.scan(lambda carry, bp: body(bp, carry), x, blocks)


# ---------------------------------------------------------------------------
# encoder (whisper)
# ---------------------------------------------------------------------------

def encode(params, frames, cfg: ArchConfig, *, remat="full",
           unroll: bool = False):
    """frames: (B, T_enc, D) precomputed frame embeddings (conv-frontend stub)."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)[None]

    def body(bp, x):
        h = norm(x, bp["ln1"], cfg.norm)
        y = mixers.attention_train(
            bp["mixer"], h, cfg, positions=_positions(cfg, x.shape[0], x.shape[1]),
            causal=False, window=None,
        )
        x = x + y
        return x + mlp(norm(x, bp["ln2"], cfg.norm), bp["ffn"], cfg.act), {}

    x, _ = _scan_blocks(params["enc_blocks"], x, body, remat=remat,
                        unroll=unroll)
    return norm(x, params["enc_final_norm"], cfg.norm)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ArchConfig, inputs):
    x = embed_tokens(inputs, params["embed"])
    if cfg.family == "vlm" and "patches" in batch:
        p = batch["patches"].astype(x.dtype)  # (B, P, D) stub embeddings
        x = jnp.concatenate([p, x[:, p.shape[1]:]], axis=1)
    if cfg.is_encdec:
        s = inputs.shape[1]
        x = x + params["pos_embed"][:s][None]
    return x


def _head(params, x, cfg: ArchConfig):
    table = params.get("lm_head", params["embed"])
    return lm_logits(norm(x, params["final_norm"], cfg.norm), table, cfg.vocab)


def _head_raw(params, x, cfg: ArchConfig):
    """Unmasked logits over the padded vocab (loss path: the pad mask is
    folded into the CE reductions instead of materializing a masked copy)."""
    table = params.get("lm_head", params["embed"])
    h = norm(x, params["final_norm"], cfg.norm)
    return jnp.einsum("...d,vd->...v", h, table)


def _streaming_ce(logits, labels, true_vocab: int):
    """Vocab-parallel-friendly CE: no gather over the (sharded) vocab axis.

    gold logit is recovered with an iota==label masked reduction and pad-ids
    are excluded from logsumexp by the same predicate — both are elementwise
    + reduce, which GSPMD keeps sharded over "model" (the gather in
    take_along_axis forced an all-gather of the f32 logits; §Perf change A).
    """
    l32 = logits.astype(jnp.float32)
    iota = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    valid = iota < true_vocab
    neg = jnp.float32(-1e30)
    m = lax.stop_gradient(jnp.max(jnp.where(valid, l32, neg), axis=-1))
    ex = jnp.exp(jnp.where(valid, l32 - m[..., None], neg))
    logz = m + jnp.log(jnp.sum(ex, axis=-1))
    gold = jnp.sum(jnp.where(iota == labels[..., None], l32, 0.0), axis=-1)
    return logz - gold


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _hidden(params, batch, cfg: ArchConfig, *, remat, unroll: bool,
            seq_shard: bool):
    """The layers' output over the inputs, and the MoE stats summed (rows)
    or maxed (per expert) over the layers ({} without experts)."""
    inputs = batch["tokens"][:, :-1] if batch["tokens"].shape[1] > 1 else batch["tokens"]
    b, s = inputs.shape
    enc = None
    if cfg.is_encdec:
        enc = encode(params, batch["frames"], cfg, remat=remat, unroll=unroll)
    x = _embed_inputs(params, batch, cfg, inputs)
    positions = _positions(cfg, b, s)
    stats = {}
    for name, blocks, c in _stacks(params, cfg):
        body = partial(
            lambda bp, x, c: _block_train(bp, x, c, positions, enc), c=c
        )
        if seq_shard:
            # §Perf change E — sequence parallelism: the per-layer residual
            # (what jax.checkpoint stores for the backward pass) is sharded
            # seq->"model", cutting the dominant activation-stash term 16x.
            # GSPMD re-gathers inside the block where attention needs full
            # seq.
            from jax.sharding import PartitionSpec as _P
            body = partial(lambda bp, x, inner: inner(
                bp, lax.with_sharding_constraint(x, _P(None, "model", None))),
                inner=body)
        if name == "dense_blocks":
            with jax.named_scope("dense_layers"):
                x, _ = _scan_blocks(blocks, x, body, remat=remat,
                                    unroll=unroll)
        else:
            x, aux = _scan_blocks(blocks, x, body, remat=remat,
                                  unroll=unroll)
            if aux:
                stats = {"moe_local_rows": jnp.sum(aux["local_rows"]),
                         "moe_max_expert_rows": jnp.max(
                             aux["max_expert_rows"]),
                         "moe_expert_chunks": jnp.sum(
                             aux["expert_chunks"])}
    return x, stats


def forward(params, batch, cfg: ArchConfig, *, remat="full",
            unroll: bool = False, head: str = "masked",
            seq_shard: bool = False):
    """Teacher-forced logits over the full input sequence."""
    x, _ = _hidden(params, batch, cfg, remat=remat, unroll=unroll,
                   seq_shard=seq_shard)
    if head == "raw":
        return _head_raw(params, x, cfg)
    return _head(params, x, cfg)


def loss_fn(params, batch, cfg: ArchConfig, *, remat="full",
            unroll: bool = False, ce: str = "gather",
            seq_shard: bool = False, with_stats: bool = False):
    """Mean next-token loss; with `with_stats`, (loss, the MoE stats of
    `_hidden`)."""
    labels = batch["tokens"][:, 1:]
    mask = None
    if cfg.family == "vlm" and "patches" in batch:
        # only text positions contribute to the LM loss
        p = batch["patches"].shape[1]
        mask = (jnp.arange(labels.shape[1]) >= p)[None, :]
    x, stats = _hidden(params, batch, cfg, remat=remat, unroll=unroll,
                       seq_shard=seq_shard)
    if ce == "streaming":
        nll = _streaming_ce(_head_raw(params, x, cfg), labels, cfg.vocab)
    else:  # "gather": the pre-§Perf baseline implementation
        logits = _head(params, x, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = logz - gold
    if mask is None:
        loss = jnp.mean(nll)
    else:
        mask = jnp.broadcast_to(mask, nll.shape).astype(jnp.float32)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return (loss, stats) if with_stats else loss


def _by_stack(per_stack: dict):
    """One stack's cache as it is; several under their stacks' names."""
    return next(iter(per_stack.values())) if len(per_stack) == 1 \
        else per_stack


def _scan_cached(blocks, x, body, unroll: bool, cache=None):
    """Run body(bp, x[, cache_l]) -> (x, cache_l) over a stack; the
    layers' caches come back stacked."""
    if unroll:
        n = jax.tree.leaves(blocks)[0].shape[0]
        outs = []
        for i in range(n):
            args = [jax.tree.map(lambda a: a[i], blocks)]
            if cache is not None:
                args.append(jax.tree.map(lambda a: a[i], cache))
            x, c = body(args[0], x, *args[1:])
            outs.append(c)
        return x, jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    if cache is None:
        return lax.scan(lambda carry, bp: body(bp, carry), x, blocks)
    return lax.scan(lambda carry, xs: body(xs[0], carry, xs[1]), x,
                    (blocks, cache))


def prefill(params, batch, cfg: ArchConfig, *, cache_len: int,
            remat="full", unroll: bool = False):
    """Consume the prompt, return (last-token logits, stacked cache)."""
    inputs = batch["tokens"]
    b, s = inputs.shape
    enc = None
    if cfg.is_encdec:
        enc = encode(params, batch["frames"], cfg, remat=remat, unroll=unroll)
    x = _embed_inputs(params, batch, cfg, inputs)
    positions = _positions(cfg, b, s)
    caches = {}
    for name, blocks, c in _stacks(params, cfg):
        x, caches[name] = _scan_cached(
            blocks, x, partial(lambda bp, x, c: _block_prefill(
                bp, x, c, positions, enc, cache_len), c=c), unroll)
    logits = _head(params, x[:, -1:], cfg)
    return logits, _by_stack(caches)


# analysis: allow[ignored-argument] `params` keeps the cache constructor
# signature parallel to prefill/decode; shapes derive from cfg alone
def init_cache(params, cfg: ArchConfig, *, batch: int, cache_len: int,
               dtype=None):
    """Zero cache pytree with stacked layer axis (for serve_step lowering)."""
    caches = {}
    if cfg.first_dense_layers:
        caches["dense_blocks"] = _init_stack_cache(
            cfg, cfg.first_dense_layers, batch, cache_len, dtype)
    caches["blocks"] = _init_stack_cache(
        cfg, cfg.num_layers - cfg.first_dense_layers, batch, cache_len, dtype)
    return _by_stack(caches)


def _init_stack_cache(cfg: ArchConfig, l: int, b: int, cache_len: int,
                      dtype):
    dtype = dtype or cfg.dtype
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    window = cfg.sliding_window
    cap = min(cache_len, window) if window else cache_len

    def attn_cache():
        return mixers.AttnCache(
            k=jnp.zeros((l, b, cap, kh, hd), dtype),
            v=jnp.zeros((l, b, cap, kh, hd), dtype),
        )

    if cfg.attention_mixer == "attn":
        cache: dict[str, Any] = {"mixer": attn_cache()}
    elif cfg.attention_mixer == "mla":
        cache = {"mixer": mixers.MLACache(
            ckv=jnp.zeros((l, b, cache_len, cfg.kv_lora_rank), dtype),
            kpe=jnp.zeros((l, b, cache_len, cfg.qk_rope_head_dim), dtype),
        )}
    elif cfg.attention_mixer == "rwkv6":
        h = cfg.num_heads
        rhd = cfg.d_model // h
        cache = {"mixer": mixers.Rwkv6Cache(
            state=jnp.zeros((l, b, h, rhd, rhd), jnp.float32),
            x_prev=jnp.zeros((l, b, cfg.d_model), dtype),
        )}
    else:  # hymba
        cache = {"mixer": mixers.HymbaCache(
            attn=attn_cache(),
            ssm_state=jnp.zeros(
                (l, b, cfg.num_heads, cfg.ssm_state, cfg.head_dim), jnp.float32
            ),
        )}
    if cfg.is_encdec:
        cache["cross"] = mixers.AttnCache(
            k=jnp.zeros((l, b, cfg.encoder_seq, kh, hd), dtype),
            v=jnp.zeros((l, b, cfg.encoder_seq, kh, hd), dtype),
        )
    return cache


def decode_step(params, cache, tokens, pos, cfg: ArchConfig, *,
                unroll: bool = False):
    """One decode step. tokens: (B, 1) int32; pos: () int32 absolute position.

    cache leaves carry a leading layer axis; each layer stack is scanned
    with its cache consumed/produced as scan xs/ys.
    """
    b = tokens.shape[0]
    x = embed_tokens(tokens, params["embed"])
    if cfg.is_encdec:
        x = x + lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1)[None]
    rope_pos = _decode_rope_positions(cfg, b, pos)
    stacks = _stacks(params, cfg)
    caches = cache if len(stacks) > 1 else {stacks[0][0]: cache}
    new = {}
    for name, blocks, c in stacks:
        x, new[name] = _scan_cached(
            blocks, x, partial(lambda bp, x, cl, c: _block_decode(
                bp, x, c, cl, pos, rope_pos), c=c), unroll, caches[name])
    logits = _head(params, x, cfg)
    return logits, _by_stack(new)
