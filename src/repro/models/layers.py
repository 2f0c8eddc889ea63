"""Shared neural-net layers, pure-functional JAX.

Conventions:
  - activations (B, S, D); attention heads (B, S, H, hd)
  - params are plain dicts of jnp arrays; init fns return (params, ...)
  - softmax / norms accumulate in f32 regardless of activation dtype
  - attention uses a streaming kv-block softmax ("flash pattern") so a
    32k-token prefill never materializes an S x S score matrix
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, bias=None, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    out = out.astype(x.dtype) * scale
    if bias is not None:
        out = out + bias
    return out


def norm(x, params, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"])
    return layernorm(x, params["scale"], params.get("bias"))


def init_norm(d: int, kind: str, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE + qwen2-vl's M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple[int, int, int]):
    """Multimodal RoPE (qwen2-vl, arXiv:2409.12191).

    positions3: (3, B, S) — temporal / height / width position ids.
    The head_dim/2 frequency channels are split into three sections; each
    section rotates by its own position stream.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    sec = jnp.asarray(
        sum(([i] * s for i, s in enumerate(sections)), []), jnp.int32
    )  # (hd/2,) section id per freq channel
    # per-channel position stream: (hd/2, B, S) -> (B, S, hd/2)
    pos = jnp.moveaxis(jnp.take(positions3, sec, axis=0), 0, -1).astype(jnp.float32)
    angles = pos * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (streaming-softmax; GQA; optional sliding window)
# ---------------------------------------------------------------------------

def _gqa_expand(k, n_rep: int):
    """(B, S, KH, hd) -> (B, S, KH*n_rep, hd) by repetition."""
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd)).reshape(
        b, s, kh * n_rep, hd
    )


def chunked_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                      q_offset: int = 0, block: int = 1024,
                      remat_blocks: bool = False):
    """Block-sparse streaming-softmax attention.

    q, k: (B, Sq|Skv, H|KH, hd); v: (B, Skv, KH, vd) with H % KH == 0;
    the value width vd may differ from hd (latent attention), and the
    scores are scaled by 1/sqrt(hd).
    q_offset: absolute position of q[0] relative to k[0] (decode/prefill).
    window: sliding-window size (None = full).
    Returns (B, Sq, H, vd).

    Queries are processed in blocks too (§Perf change G): for each q block
    only the kv blocks that are not FULLY masked are visited — upper-triangle
    blocks are skipped under causal masking (~2x at long seq) and
    out-of-window blocks under SWA (Skv/window x, e.g. 16x for hymba's
    window-1024 at 4k context). Partially-masked diagonal blocks keep the
    exact elementwise mask, so results are identical to dense masking.

    remat_blocks: recompute each kv block's scores in the backward pass
    instead of keeping them: the backward then holds the running
    statistics per block, not its (B, H, block, block) probabilities
    (128 MiB a block at 16 heads, 36 blocks a layer at 8k context).
    """
    b, sq, h, hd = q.shape
    skv, kh, vd = k.shape[1], k.shape[2], v.shape[-1]
    k = _gqa_expand(k, h // kh)
    v = _gqa_expand(v, h // kh)
    scale = 1.0 / math.sqrt(hd)

    block = min(block, skv)
    nblk = (skv + block - 1) // block
    pad = nblk * block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, block, h, hd)
    vb = v.reshape(b, nblk, block, h, vd)

    qb_size = min(block, sq)
    nqb = (sq + qb_size - 1) // qb_size
    qpad = nqb * qb_size - sq
    q32 = q.astype(jnp.float32) * scale
    if qpad:
        q32 = jnp.pad(q32, ((0, 0), (0, qpad), (0, 0), (0, 0)))

    from repro.models import flags

    def make_body(q_blk, q_pos):
        def body(carry, blk):
            m_prev, l_prev, acc = carry
            kj, vj, j = blk
            kv_pos = j * block + jnp.arange(block)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kj.astype(jnp.float32))
            mask = jnp.ones((qb_size, block), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - kv_pos[None, :] < window
            mask &= (kv_pos < skv)[None, :]
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            # guard fully-masked rows (m=-inf): exp(-inf - -inf) -> safe m
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(
                jnp.where(jnp.isfinite(m_prev), m_prev - m_safe, -jnp.inf))
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            # §Perf change F: probabilities feed the MXU in bf16 (the
            # TPU-native dot input dtype); max/sum/acc statistics stay f32.
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(jnp.bfloat16),
                vj.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc), None

        return jax.checkpoint(body) if remat_blocks else body

    outs = []
    for qi in range(nqb):
        q_blk = lax.slice_in_dim(q32, qi * qb_size, (qi + 1) * qb_size, axis=1)
        q_lo = q_offset + qi * qb_size
        q_hi = q_offset + min((qi + 1) * qb_size, sq) - 1
        j_lo = 0 if window is None else max(0, (q_lo - window + 1) // block)
        j_hi = min(nblk - 1, q_hi // block) if causal else nblk - 1
        j_hi = max(j_hi, j_lo)
        idx = jnp.arange(j_lo, j_hi + 1)
        m0 = jnp.full((b, h, qb_size), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, qb_size), jnp.float32)
        acc0 = jnp.zeros((b, h, qb_size, vd), jnp.float32)
        (m, l, acc), _ = lax.scan(
            make_body(q_blk, q_offset + qi * qb_size + jnp.arange(qb_size)),
            (m0, l0, acc0),
            (kb[:, j_lo:j_hi + 1].swapaxes(0, 1),
             vb[:, j_lo:j_hi + 1].swapaxes(0, 1), idx),
            unroll=flags.inner_scan_unroll(),
        )
        outs.append(acc / jnp.maximum(l, 1e-30)[..., None])
    out = jnp.concatenate(outs, axis=2) if nqb > 1 else outs[0]
    out = out[:, :, :sq]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, Sq, H, vd)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int | None = None):
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, hd); caches: (B, C, KH, hd); cache_len: () int32 — number of
    valid entries (for ring buffers C == window and all entries valid once
    wrapped; masking handles the warmup).

    §Perf change H: GQA is expressed as a grouped einsum (q reshaped to
    (B, KH, rep, hd)) instead of materially broadcasting the cache KH -> H,
    and both dots run on bf16 inputs with f32 accumulation. Without this,
    GSPMD's cheapest strategy was to all-gather an f32 COPY of the whole
    cache over the model axis (2 x 1.07 GB per layer per token on
    deepseek-67b decode_32k). Scores stay sharded over the cache axis; the
    softmax reductions become small psums.
    """
    b, _, h, hd = q.shape
    c, kh = k_cache.shape[1], k_cache.shape[2]
    rep = h // kh
    qg = q.reshape(b, kh, rep, hd).astype(jnp.bfloat16)
    s = jnp.einsum("bkrd,bckd->bkrc", qg, k_cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(hd)
    pos = jnp.arange(c)
    valid = pos[None, None, None, :] < cache_len
    if window is not None:
        valid &= pos[None, None, None, :] >= cache_len - window
    s = jnp.where(valid, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrc,bckd->bkrd", p.astype(jnp.bfloat16),
                     v_cache.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# dense projections / FFN
# ---------------------------------------------------------------------------

def linear(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


def mlp(x, p, act: str):
    if act == "swiglu":
        return linear(jax.nn.silu(linear(x, p["w_gate"])) * linear(x, p["w_up"]),
                      p["w_down"])
    if act == "relu2":  # RWKV channel-mix: relu(xW)^2
        h = jnp.square(jax.nn.relu(linear(x, p["w_up"])))
        return linear(h, p["w_down"])
    h = jax.nn.gelu(linear(x, p["w_up"], p.get("b_up")))
    return linear(h, p["w_down"], p.get("b_down"))


def init_linear(key, d_in, d_out, dtype, bias=False, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_mlp(key, d, f, act, dtype):
    ks = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    p = {
        "w_up": jax.random.normal(ks[0], (d, f), dtype) * s_in,
        "w_down": jax.random.normal(ks[1], (f, d), dtype) * s_out,
    }
    if act == "swiglu":
        p["w_gate"] = jax.random.normal(ks[2], (d, f), dtype) * s_in
    return p


def embed_tokens(tokens, table):
    return jnp.take(table, tokens, axis=0)


def lm_logits(x, table, true_vocab: int):
    """Project to (padded) vocab and mask pad ids to -inf."""
    logits = jnp.einsum("...d,vd->...v", x, table)
    v_pad = table.shape[0]
    if v_pad > true_vocab:
        neg = jnp.full((v_pad - true_vocab,), -1e30, logits.dtype)
        logits = logits.at[..., true_vocab:].set(neg)
    return logits


def cross_entropy(logits, labels, true_vocab: int):
    """Mean CE in f32; labels int32 (..., ) in [0, true_vocab).

    Masks the padded vocab tail itself (idempotent after `lm_logits`), so
    the logsumexp never includes garbage columns of an unmasked head."""
    logits = logits.astype(jnp.float32)
    v_pad = logits.shape[-1]
    if v_pad > true_vocab:
        neg = jnp.full((v_pad - true_vocab,), -1e30, logits.dtype)
        logits = logits.at[..., true_vocab:].set(neg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
