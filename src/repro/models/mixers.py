"""Sequence mixers: softmax attention (GQA/RoPE/M-RoPE/SWA), latent
attention (MLA), RWKV6, Hymba.

Every mixer exposes the same three entry points so the block assembly in
`transformer.py` stays family-agnostic:

    init_<name>(key, cfg)                      -> params (no layer axis)
    <name>_train(params, x, cfg, *, pos, ...)  -> y                (full seq)
    <name>_prefill(params, x, cfg, *, pos)     -> (y, cache)       (build cache)
    <name>_decode(params, x, cfg, cache, pos)  -> (y, cache)       (1 token)

Caches are per-layer pytrees; `transformer.py` stacks them over layers.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ArchConfig
from repro.models.layers import (
    apply_mrope,
    apply_rope,
    chunked_attention,
    decode_attention,
    linear,
    rmsnorm,
)
from repro.models.linear_attention import (
    chunked_linear_attention,
    linear_attention_decode,
)


def _normal(key, shape, dtype, fan_in):
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


# ---------------------------------------------------------------------------
# softmax attention (dense / VLM / encoder-decoder self-attention)
# ---------------------------------------------------------------------------

class AttnCache(NamedTuple):
    k: jax.Array  # (B, C, KH, hd)
    v: jax.Array  # (B, C, KH, hd)


def init_attention(key, cfg: ArchConfig, *, d_model: int | None = None):
    d = d_model or cfg.d_model
    hd, qh, kh = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": _normal(ks[0], (d, qh * hd), cfg.dtype, d),
        "wk": _normal(ks[1], (d, kh * hd), cfg.dtype, d),
        "wv": _normal(ks[2], (d, kh * hd), cfg.dtype, d),
        "wo": _normal(ks[3], (qh * hd, d), cfg.dtype, qh * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qh * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((kh * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((kh * hd,), cfg.dtype)
    return p


def _qkv(p, x, cfg: ArchConfig):
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(b, s, cfg.num_heads, hd)
    k = linear(x, p["wk"], p.get("bk")).reshape(b, s, cfg.num_kv_heads, hd)
    v = linear(x, p["wv"], p.get("bv")).reshape(b, s, cfg.num_kv_heads, hd)
    return q, k, v


def _rotate(q, k, cfg: ArchConfig, positions):
    """positions: (B, S) int32, or (3, B, S) for M-RoPE."""
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def attention_train(p, x, cfg: ArchConfig, *, positions, causal: bool = True,
                    window: int | None = "cfg"):
    if window == "cfg":
        window = cfg.sliding_window
    q, k, v = _qkv(p, x, cfg)
    q, k = _rotate(q, k, cfg, positions)
    out = chunked_attention(q, k, v, causal=causal, window=window)
    b, s = x.shape[:2]
    return linear(out.reshape(b, s, -1), p["wo"])


def attention_prefill(p, x, cfg: ArchConfig, *, positions, cache_len: int,
                      window: int | None = "cfg"):
    """Run causal attention over the prompt and leave a KV cache of capacity
    `cache_len` (ring-buffered when `window` is set)."""
    if window == "cfg":
        window = cfg.sliding_window
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    q, k = _rotate(q, k, cfg, positions)
    out = chunked_attention(q, k, v, causal=True, window=window)
    cap = min(cache_len, window) if window is not None else cache_len
    kc = jnp.zeros((b, cap, cfg.num_kv_heads, cfg.head_dim), x.dtype)
    vc = jnp.zeros_like(kc)
    if window is None or s <= cap:
        take = min(s, cap)
        kc = lax.dynamic_update_slice(kc, k[:, -take:], (0, 0, 0, 0))
        vc = lax.dynamic_update_slice(vc, v[:, -take:], (0, 0, 0, 0))
    else:
        # ring buffer: last `cap` tokens, placed at their pos % cap slots
        tail_k, tail_v = k[:, -cap:], v[:, -cap:]
        slots = (jnp.arange(s - cap, s)) % cap
        kc = kc.at[:, slots].set(tail_k)
        vc = vc.at[:, slots].set(tail_v)
    y = linear(out.reshape(b, s, -1), p["wo"])
    return y, AttnCache(kc, vc)


def attention_decode(p, x, cfg: ArchConfig, cache: AttnCache, pos,
                     window: int | None = "cfg", rope_positions=None):
    """x: (B, 1, D); pos: () int32 — absolute position of this token.

    rope_positions overrides the rotation stream (M-RoPE text positions
    differ from the raw cache position); cache slots always use `pos`.
    """
    if window == "cfg":
        window = cfg.sliding_window
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    if rope_positions is None:
        rope_positions = jnp.broadcast_to(pos, (b, 1))
        if cfg.mrope_sections is not None:
            rope_positions = jnp.broadcast_to(pos, (3, b, 1))
    q, k = _rotate(q, k, cfg, rope_positions)
    cap = cache.k.shape[1]
    slot = pos % cap if window is not None else pos
    kc = lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
    vc = lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
    n_valid = jnp.minimum(pos + 1, cap) if window is not None else pos + 1
    # ring buffer: once wrapped, every slot is within the window; masking by
    # count handles warmup (slots >= n_valid are zeros).
    out = decode_attention(q, kc, vc, n_valid, window=None)
    y = linear(out.reshape(b, 1, -1), p["wo"])
    return y, AttnCache(kc, vc)


# ---------------------------------------------------------------------------
# latent attention (MLA: DeepSeek-V2/V3 with q_lora_rank null)
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    ckv: jax.Array  # (B, C, kv_lora_rank) normalised latent
    kpe: jax.Array  # (B, C, qk_rope_head_dim) rotated key part, all heads'


def init_mla(key, cfg: ArchConfig):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _normal(ks[0], (d, h * qk), cfg.dtype, d),
        "wkv_a": _normal(ks[1], (d, r + cfg.qk_rope_head_dim), cfg.dtype, d),
        "kv_norm": {"scale": jnp.ones((r,), cfg.dtype)},
        "wkv_b": _normal(ks[2], (r, h * (cfg.qk_nope_head_dim
                                         + cfg.v_head_dim)), cfg.dtype, r),
        "wo": _normal(ks[3], (h * cfg.v_head_dim, d), cfg.dtype,
                      h * cfg.v_head_dim),
    }


def _mla_queries(p, x, cfg: ArchConfig, positions):
    """(B, S, H, nope + rope): each head's query, its rope part rotated."""
    b, s, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = linear(x, p["wq"]).reshape(b, s, cfg.num_heads, -1)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    return jnp.concatenate([q[..., :nope], q_pe], axis=-1)


def _mla_latent(p, x, cfg: ArchConfig, positions):
    """What the cache holds per position: the normalised latent (B, S, r)
    and the rotated key part shared by the heads (B, S, rope)."""
    kv = linear(x, p["wkv_a"])
    ckv = rmsnorm(kv[..., :cfg.kv_lora_rank], p["kv_norm"]["scale"])
    kpe = apply_rope(kv[..., None, cfg.kv_lora_rank:], positions,
                     cfg.rope_theta)
    return ckv, kpe[:, :, 0]


def _mla_attend(p, q, ckv, kpe, cfg: ArchConfig):
    """Causal attention of q over keys and values expanded from the
    latent; scores at 1/sqrt(nope + rope), values v_head_dim wide. The
    kv blocks' scores are recomputed in the backward pass: keeping them,
    Moonlight's chip-share step at 2 x 8192 tokens needs 18.73 GiB of a
    TPU v5e's 15.75 and does not compile (8.70 GiB of temporaries with
    the recompute)."""
    b, s, h, _ = q.shape
    nope = cfg.qk_nope_head_dim
    kv = linear(ckv, p["wkv_b"]).reshape(b, s, h, -1)
    k_pe = jnp.broadcast_to(kpe[:, :, None], (b, s, h, kpe.shape[-1]))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    out = chunked_attention(q, k, kv[..., nope:], causal=True,
                            remat_blocks=True)
    return linear(out.reshape(b, s, -1), p["wo"])


def mla_train(p, x, cfg: ArchConfig, *, positions):
    with jax.named_scope("mla"):
        q = _mla_queries(p, x, cfg, positions)
        ckv, kpe = _mla_latent(p, x, cfg, positions)
        return _mla_attend(p, q, ckv, kpe, cfg)


def mla_prefill(p, x, cfg: ArchConfig, *, positions, cache_len: int):
    """Attention over the prompt; the cache keeps the latent and the
    rotated key part of its positions (capacity `cache_len`)."""
    b, s, _ = x.shape
    with jax.named_scope("mla"):
        q = _mla_queries(p, x, cfg, positions)
        ckv, kpe = _mla_latent(p, x, cfg, positions)
        y = _mla_attend(p, q, ckv, kpe, cfg)
    cache = MLACache(
        jnp.zeros((b, cache_len, cfg.kv_lora_rank), x.dtype).at[:, :s].set(
            ckv),
        jnp.zeros((b, cache_len, cfg.qk_rope_head_dim), x.dtype).at[
            :, :s].set(kpe))
    return y, cache


def mla_decode(p, x, cfg: ArchConfig, cache: MLACache, pos):
    """x: (B, 1, D). Attention straight over the latent cache: the key
    expansion is absorbed into the query and the value expansion applied
    after the weighted sum, so no per-position key or value is built."""
    b = x.shape[0]
    h, nope, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    pos_b = jnp.broadcast_to(pos, (b, 1))
    with jax.named_scope("mla"):
        q = _mla_queries(p, x, cfg, pos_b)[:, 0].astype(jnp.float32)
        ckv, kpe = _mla_latent(p, x, cfg, pos_b)
        ckv_c = lax.dynamic_update_slice(cache.ckv, ckv, (0, pos, 0))
        kpe_c = lax.dynamic_update_slice(cache.kpe, kpe, (0, pos, 0))
        w = p["wkv_b"].astype(jnp.float32).reshape(r, h, -1)
        c32, pe32 = ckv_c.astype(jnp.float32), kpe_c.astype(jnp.float32)
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :nope], w[..., :nope])
        s = (jnp.einsum("bhr,bcr->bhc", q_lat, c32)
             + jnp.einsum("bhp,bcp->bhc", q[..., nope:], pe32))
        s = s / math.sqrt(q.shape[-1])
        valid = jnp.arange(c32.shape[1])[None, None] <= pos
        probs = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("bhc,bcr->bhr", probs, c32)
        out = jnp.einsum("bhr,rhv->bhv", o_lat, w[..., nope:])
        y = linear(out.reshape(b, 1, -1).astype(x.dtype), p["wo"])
    return y, MLACache(ckv_c, kpe_c)


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ArchConfig):
    return init_attention(key, cfg)


def cross_attention_train(p, x, enc, cfg: ArchConfig):
    """x: (B, S, D) decoder stream; enc: (B, T_enc, D) encoder output."""
    b, s, _ = x.shape
    t = enc.shape[1]
    hd = cfg.head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(b, s, cfg.num_heads, hd)
    k = linear(enc, p["wk"], p.get("bk")).reshape(b, t, cfg.num_kv_heads, hd)
    v = linear(enc, p["wv"], p.get("bv")).reshape(b, t, cfg.num_kv_heads, hd)
    out = chunked_attention(q, k, v, causal=False)
    return linear(out.reshape(b, s, -1), p["wo"])


def cross_attention_cache(p, enc, cfg: ArchConfig) -> AttnCache:
    b, t, _ = enc.shape
    hd = cfg.head_dim
    k = linear(enc, p["wk"], p.get("bk")).reshape(b, t, cfg.num_kv_heads, hd)
    v = linear(enc, p["wv"], p.get("bv")).reshape(b, t, cfg.num_kv_heads, hd)
    return AttnCache(k, v)


def cross_attention_decode(p, x, cfg: ArchConfig, cache: AttnCache):
    b = x.shape[0]
    hd = cfg.head_dim
    q = linear(x, p["wq"], p.get("bq")).reshape(b, 1, cfg.num_heads, hd)
    t = cache.k.shape[1]
    out = decode_attention(q, cache.k, cache.v, jnp.int32(t))
    return linear(out.reshape(b, 1, -1), p["wo"])


# ---------------------------------------------------------------------------
# RWKV6 ("Finch", arXiv:2404.05892) — attention-free, data-dependent decay
# ---------------------------------------------------------------------------

class Rwkv6Cache(NamedTuple):
    state: jax.Array  # (B, H, dk, hd) linear-attention state
    x_prev: jax.Array  # (B, D) last token's input (token shift)


DECAY_LORA = 64


def init_rwkv6(key, cfg: ArchConfig):
    d, h = cfg.d_model, cfg.num_heads
    hd = d // h  # rwkv head size
    ks = jax.random.split(key, 8)
    p = {
        # token-shift lerp coefficients per stream (static mu; Finch makes
        # these data-dependent via lora — we keep the decay lora, the hallmark)
        "mu": jnp.full((5, d), 0.5, cfg.dtype),  # r,k,v,g,w order
        "wr": _normal(ks[0], (d, d), cfg.dtype, d),
        "wk": _normal(ks[1], (d, d), cfg.dtype, d),
        "wv": _normal(ks[2], (d, d), cfg.dtype, d),
        "wg": _normal(ks[3], (d, d), cfg.dtype, d),
        "wo": _normal(ks[4], (d, d), cfg.dtype, d),
        # data-dependent decay: w = -exp(w0 + tanh(x A) B)  (per channel)
        "w0": jnp.full((d,), -2.0, jnp.float32),
        "wA": _normal(ks[5], (d, DECAY_LORA), cfg.dtype, d),
        "wB": _normal(ks[6], (DECAY_LORA, d), cfg.dtype, DECAY_LORA) * 0.1,
        # per-(head, channel) bonus u on the current token
        "u": jax.random.normal(ks[7], (h, hd), jnp.float32) * 0.1,
        "ln_out": jnp.ones((h, hd), jnp.float32),  # per-head groupnorm scale
    }
    return p


def _rwkv6_streams(p, x, x_prev, cfg: ArchConfig):
    """Token-shifted projection streams. x: (B, S, D); x_prev: (B, S, D) with
    x_prev[:, t] = x[:, t-1] (caller supplies the shifted stream)."""
    mu = p["mu"].astype(jnp.float32)
    x32, xp32 = x.astype(jnp.float32), x_prev.astype(jnp.float32)
    mix = lambda i: (x32 + (xp32 - x32) * mu[i]).astype(x.dtype)
    b, s, d = x.shape
    h = cfg.num_heads
    hd = d // h
    r = linear(mix(0), p["wr"]).reshape(b, s, h, hd)
    k = linear(mix(1), p["wk"]).reshape(b, s, h, hd)
    v = linear(mix(2), p["wv"]).reshape(b, s, h, hd)
    g = jax.nn.silu(linear(mix(3), p["wg"]))
    xw = mix(4)
    lora = jnp.tanh(linear(xw, p["wA"])).astype(jnp.float32)
    log_decay = -jnp.exp(
        p["w0"] + (lora @ p["wB"].astype(jnp.float32))
    )  # (B, S, D), strictly negative — data-dependent decay
    log_decay = log_decay.reshape(b, s, h, hd)
    return r, k, v, g, log_decay


def _rwkv6_out(p, wkv, g, cfg: ArchConfig):
    """Per-head groupnorm on wkv, gate, output projection."""
    b, s, h, hd = wkv.shape
    w32 = wkv.astype(jnp.float32)
    mean = jnp.mean(w32, axis=-1, keepdims=True)
    var = jnp.var(w32, axis=-1, keepdims=True)
    normed = (w32 - mean) * lax.rsqrt(var + 1e-5) * p["ln_out"]
    y = normed.reshape(b, s, h * hd).astype(g.dtype) * g
    return linear(y, p["wo"])


def rwkv6_train(p, x, cfg: ArchConfig):
    x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    r, k, v, g, ld = _rwkv6_streams(p, x, x_prev, cfg)
    wkv, _ = chunked_linear_attention(
        r, k, v, ld, bonus=p["u"], inclusive=False
    )
    return _rwkv6_out(p, wkv, g, cfg)


def rwkv6_prefill(p, x, cfg: ArchConfig):
    x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    r, k, v, g, ld = _rwkv6_streams(p, x, x_prev, cfg)
    wkv, state = chunked_linear_attention(
        r, k, v, ld, bonus=p["u"], inclusive=False
    )
    y = _rwkv6_out(p, wkv, g, cfg)
    return y, Rwkv6Cache(state=state, x_prev=x[:, -1])


def rwkv6_decode(p, x, cfg: ArchConfig, cache: Rwkv6Cache):
    """x: (B, 1, D)."""
    b, _, d = x.shape
    x_prev = cache.x_prev[:, None]
    r, k, v, g, ld = _rwkv6_streams(p, x, x_prev, cfg)
    out, state = linear_attention_decode(
        r[:, 0], k[:, 0], v[:, 0], ld[:, 0], cache.state.astype(jnp.float32),
        bonus=p["u"], inclusive=False,
    )
    y = _rwkv6_out(p, out[:, None], g, cfg)
    return y, Rwkv6Cache(state=state, x_prev=x[:, 0])


# ---------------------------------------------------------------------------
# Hymba (arXiv:2411.13676) — parallel attention + Mamba-2/SSD heads per layer
# ---------------------------------------------------------------------------

class HymbaCache(NamedTuple):
    attn: AttnCache
    ssm_state: jax.Array  # (B, H, N, hd)


def init_hymba(key, cfg: ArchConfig):
    d, h, hd, n = cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.ssm_state
    ks = jax.random.split(key, 6)
    p = {"attn": init_attention(ks[0], cfg)}
    # SSD heads: values x (H, hd), input/output gates B_t, C_t (H, N), dt (H,)
    p["ssm"] = {
        "wx": _normal(ks[1], (d, h * hd), cfg.dtype, d),
        "wbc": _normal(ks[2], (d, h * 2 * n), cfg.dtype, d),
        "wdt": _normal(ks[3], (d, h), cfg.dtype, d),
        "a_log": jnp.zeros((h,), jnp.float32),
        "ln": jnp.ones((h, hd), jnp.float32),  # per-head norm before fusion
    }
    # shared output projection over the fused (attn + ssm) heads
    p["wo_fused"] = _normal(ks[4], (h * hd, d), cfg.dtype, h * hd)
    p["attn"].pop("wo")  # fused projection replaces the attention-only wo
    p["ln_attn"] = jnp.ones((h, hd), jnp.float32)
    return p


def _hymba_ssm_streams(p, x, cfg: ArchConfig):
    b, s, d = x.shape
    h, hd, n = cfg.num_heads, cfg.head_dim, cfg.ssm_state
    sp = p["ssm"]
    xv = linear(x, sp["wx"]).reshape(b, s, h, hd)
    bc = linear(x, sp["wbc"]).reshape(b, s, h, 2 * n)
    b_t, c_t = jnp.split(bc, 2, axis=-1)  # (B,S,H,N) each
    dt = jax.nn.softplus(linear(x, sp["wdt"]).astype(jnp.float32))  # (B,S,H)
    log_decay = -jnp.exp(sp["a_log"]) * dt  # scalar-per-head decay <= 0
    # SSD discretization: inputs scaled by dt
    xv = (xv.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    return c_t, b_t, xv, log_decay


def _headnorm(y, scale):
    y32 = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(y32), axis=-1, keepdims=True)
    return y32 * lax.rsqrt(var + 1e-6) * scale


def _hymba_fuse(p, attn_out, ssm_out, x_dtype, b, s):
    """Mean-fuse the two normalized head groups, shared output projection."""
    a = _headnorm(attn_out, p["ln_attn"])
    m = _headnorm(ssm_out, p["ssm"]["ln"])
    fused = (0.5 * (a + m)).astype(x_dtype).reshape(b, s, -1)
    return linear(fused, p["wo_fused"])


def hymba_train(p, x, cfg: ArchConfig, *, positions):
    b, s, _ = x.shape
    q, k, v = _qkv(p["attn"], x, cfg)
    q, k = _rotate(q, k, cfg, positions)
    attn_out = chunked_attention(q, k, v, causal=True, window=cfg.sliding_window)
    c_t, b_t, xv, ld = _hymba_ssm_streams(p, x, cfg)
    ssm_out, _ = chunked_linear_attention(c_t, b_t, xv, ld, inclusive=True)
    return _hymba_fuse(p, attn_out, ssm_out, x.dtype, b, s)


def hymba_prefill(p, x, cfg: ArchConfig, *, positions, cache_len: int):
    b, s, _ = x.shape
    q, k, v = _qkv(p["attn"], x, cfg)
    q, k = _rotate(q, k, cfg, positions)
    attn_out = chunked_attention(q, k, v, causal=True, window=cfg.sliding_window)
    window = cfg.sliding_window or cache_len
    cap = min(cache_len, window)
    kc = jnp.zeros((b, cap, cfg.num_kv_heads, cfg.head_dim), x.dtype)
    vc = jnp.zeros_like(kc)
    take = min(s, cap)
    slots = jnp.arange(s - take, s) % cap
    kc = kc.at[:, slots].set(k[:, -take:])
    vc = vc.at[:, slots].set(v[:, -take:])
    c_t, b_t, xv, ld = _hymba_ssm_streams(p, x, cfg)
    ssm_out, state = chunked_linear_attention(c_t, b_t, xv, ld, inclusive=True)
    y = _hymba_fuse(p, attn_out, ssm_out, x.dtype, b, s)
    return y, HymbaCache(AttnCache(kc, vc), state)


def hymba_decode(p, x, cfg: ArchConfig, cache: HymbaCache, pos):
    b = x.shape[0]
    q, k, v = _qkv(p["attn"], x, cfg)
    pos_b = jnp.broadcast_to(pos, (b, 1))
    q, k = _rotate(q, k, cfg, pos_b)
    cap = cache.attn.k.shape[1]
    slot = pos % cap
    kc = lax.dynamic_update_slice(cache.attn.k, k, (0, slot, 0, 0))
    vc = lax.dynamic_update_slice(cache.attn.v, v, (0, slot, 0, 0))
    n_valid = jnp.minimum(pos + 1, cap)
    attn_out = decode_attention(q, kc, vc, n_valid)
    c_t, b_t, xv, ld = _hymba_ssm_streams(p, x, cfg)
    ssm_out, state = linear_attention_decode(
        c_t[:, 0], b_t[:, 0], xv[:, 0], ld[:, 0],
        cache.ssm_state.astype(jnp.float32), inclusive=True,
    )
    y = _hymba_fuse(p, attn_out, ssm_out[:, None], x.dtype, b, 1)
    return y, HymbaCache(AttnCache(kc, vc), state)
