"""Plain reference of Moonlight-16B-A3B's chip share (DeepSeek-V3's
layers): the parameter tree, the loss in float32 at the highest matmul
precision, the model FLOPs per token, and the work of the expert kernels.

A configuration file names this model with `"reference": "moonlight"`.
Like every module of `references/`, it exports `param_shapes(m)`,
`loss(params, tokens, m, fp8=False)` and `flops_per_token(m, seq)`, where
`m` is the file's `model` section, and imports nothing of the program or
of the harness.

The model: an embedding, `first_dense_layers` dense layers and then
expert layers, a final RMS norm and an untied LM head over the vocabulary
slice. Every layer is pre-norm: latent attention, then the feed-forward.

- Latent attention (q_lora_rank null): q = h wq, per head nope + rope
  wide; [c, k_pe] = h wkv_a, c normalised (RMS norm of its own scale);
  [k_nope, v] = c wkv_b per head; RoPE on the rope-wide parts of q and of
  k_pe, whose one part every head shares; causal softmax at
  1/sqrt(nope + rope); out = concat(heads) wo.
- The dense layer: SwiGLU of width dense_d_ff.
- An expert layer: sigmoid scores over all the router's outputs; the top
  k by score plus the selection bias (which weights nothing); weights the
  chosen scores over their sum times routed_scaling. The held experts are
  the first num_experts of the router's: each token gets the weighted sum
  of the held experts it chose (SwiGLU of width d_ff), and nothing for
  those it chose elsewhere; plus the shared experts, one SwiGLU of width
  shared_expert_ff.

Departures from the published model, which the program makes too and the
reference therefore follows: norm epsilon 1e-6 (published 1e-5),
rotate-halves RoPE (the published code permutes interleaved pairs first),
no auxiliary sequence loss, no load-based update of the selection bias.

`fp8=True` computes every matmul (the router's too, and the embedding
lookup) on float8 e4m3 operands: the control, one precision below the
bfloat16 the configuration states.

Model FLOPs (the `mfu` convention): 6 FLOPs per parameter a token meets
in a matrix multiplication, the routed experts counted at their expected
share, experts_per_token * num_experts / router_experts per token; plus
causal attention over on average seq/2 keys, 2 * (seq/2) * heads *
(qk width + v width) forward, times 3 with the backward. Norms, the
embedding lookup, the routing's sort and the loss are not counted, nor
any recomputation.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
F8 = jnp.float8_e4m3fn
HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 512  # query rows per attention block
CE_CHUNK = 1024  # positions per block of the loss
MOE_CHUNK = 1024  # tokens per block of the feed-forward layers

# Layers run under `lax.scan` and the blocks of attention, feed-forward
# and loss under `lax.map`, each recomputed in the backward pass, so the
# float32 pass over 2 x 8192 tokens compiles once per block shape and
# keeps one block's intermediates at a time.


def _widths(m: dict):
    return (m["d_model"], m["num_heads"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"])


def param_shapes(m: dict) -> dict:
    """The parameter tree (names, stacked layer axes, shapes, dtype)."""
    dt = jnp.dtype(m["dtype"])
    d, h, nope, rope, vd, r = _widths(m)
    n_dense = m["first_dense_layers"]
    n_moe = m["num_layers"] - n_dense
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dt)

    def mixer(L):
        return {"wq": s(L, d, h * (nope + rope)), "wkv_a": s(L, d, r + rope),
                "kv_norm": {"scale": s(L, r)},
                "wkv_b": s(L, r, h * (nope + vd)), "wo": s(L, h * vd, d)}

    def swiglu(*lead, f):
        return {"w_gate": s(*lead, d, f), "w_up": s(*lead, d, f),
                "w_down": s(*lead, f, d)}

    def block(L, ffn):
        return {"ln1": {"scale": s(L, d)}, "ln2": {"scale": s(L, d)},
                "mixer": mixer(L), "ffn": ffn}

    e, n_router = m["num_experts"], m["router_experts"]
    moe = {"router": s(n_moe, d, n_router), "b_router": s(n_moe, n_router),
           "shared": swiglu(n_moe, f=m["shared_expert_ff"]),
           **swiglu(n_moe, e, f=m["d_ff"])}
    return {"embed": s(m["vocab"], d), "lm_head": s(m["vocab"], d),
            "final_norm": {"scale": s(d)},
            "dense_blocks": block(n_dense, swiglu(n_dense, f=m["dense_d_ff"])),
            "blocks": block(n_moe, moe)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _mm(spec, a, b, fp8):
    if fp8:
        return jnp.einsum(spec, a.astype(F8), b.astype(F8),
                          preferred_element_type=F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * scale


def _rope(x, theta):
    """x: (b, s, heads, w); rotate the two halves of each head."""
    w = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, w, 2, dtype=np.float64) / w)
    ang = np.arange(x.shape[1])[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None]
    x1, x2 = x[..., :w // 2], x[..., w // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _chunks(f, size, *xs):
    """f over blocks of `size` rows of each x (axis 0), one block at a
    time and recomputed in the backward pass; the blocks' results come
    back joined on axis 0. The block's first row index is f's last
    argument."""
    n = xs[0].shape[0]
    size = min(size, n)
    if n % size:
        raise ValueError(f"{n} rows do not split into blocks of {size}")
    body = jax.checkpoint(f)
    blocks = [x.reshape((n // size, size) + x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda a: body(*a[:-1], a[-1]),
                      (*blocks, jnp.arange(0, n, size)))
    return jax.tree.map(lambda y: y.reshape((n,) + y.shape[2:]), out)


def _attend(q, k, v, fp8, q0):
    """Causal softmax attention of the query rows [q0, q0 + len) (axis 0
    of q; k and v (keys, batch, heads, width))."""
    scores = _mm("qbhd,kbhd->bhqk", q, k, fp8) / math.sqrt(q.shape[-1])
    mask = jnp.arange(k.shape[0])[None, :] <= q0 + jnp.arange(
        q.shape[0])[:, None]
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return _mm("bhqk,kbhd->qbhd", jax.nn.softmax(scores, axis=-1), v, fp8)


def _mla(p, h, m, fp8):
    b, s, _ = h.shape
    _, nh, nope, rope, vd, r = _widths(m)
    q = _mm("bsd,df->bsf", h, p["wq"], fp8).reshape(b, s, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              m["rope_theta"])], -1)
    kv = _mm("bsd,df->bsf", h, p["wkv_a"], fp8)
    c = _norm(kv[..., :r], p["kv_norm"]["scale"])
    k_pe = _rope(kv[..., None, r:], m["rope_theta"])
    kvb = _mm("bsr,rf->bsf", c, p["wkv_b"], fp8).reshape(b, s, nh, nope + vd)
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(k_pe, (b, s, nh, rope))], -1)
    seq_major = lambda x: jnp.swapaxes(x, 0, 1)
    k, v = seq_major(k), seq_major(kvb[..., nope:])
    out = _chunks(lambda qb, q0: _attend(qb, k, v, fp8, q0), Q_CHUNK,
                  seq_major(q))
    return _mm("bsf,fd->bsd", seq_major(out).reshape(b, s, nh * vd),
               p["wo"], fp8)


def _swiglu(p, x, fp8):
    """x (..., d); over blocks of tokens where x is a (b, s, d) batch."""
    def ffn(xb, _):
        a = jax.nn.silu(_mm("nd,df->nf", xb, p["w_gate"], fp8)) \
            * _mm("nd,df->nf", xb, p["w_up"], fp8)
        return _mm("nf,fd->nd", a, p["w_down"], fp8)
    return _chunks(ffn, MOE_CHUNK, x.reshape(-1, x.shape[-1])).reshape(
        x.shape)


def _held_experts(p, fp8, x, comb, _):
    """x (n, d) tokens, comb (n, held) their weights on the held experts
    (0 where not chosen) -> the weighted sum of the held experts' outputs."""
    a = jax.nn.silu(_mm("nd,edf->nef", x, p["w_gate"], fp8)) \
        * _mm("nd,edf->nef", x, p["w_up"], fp8)
    y = _mm("nef,efd->ned", a, p["w_down"], fp8)
    return jnp.einsum("ned,ne->nd", y, comb, precision=HIGHEST)


def _moe(p, h, m, fp8):
    b, s, d = h.shape
    k, held = m["experts_per_token"], m["num_experts"]
    x = h.reshape(b * s, d)
    scores = jax.nn.sigmoid(_mm("nd,de->ne", x, p["router"], fp8))
    _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(p["b_router"]), k)
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * m["routed_scaling"]
    comb = jnp.sum(jax.nn.one_hot(top, m["router_experts"])[..., :held]
                   * w[..., None], axis=1)
    y = _chunks(partial(_held_experts, p, fp8), MOE_CHUNK, x, comb)
    return y.reshape(b, s, d) + _swiglu(p["shared"], h, fp8)


def _layer(p, x, m, fp8, moe):
    x = x + _mla(p["mixer"], _norm(x, p["ln1"]["scale"]), m, fp8)
    h = _norm(x, p["ln2"]["scale"])
    return x + (_moe(p["ffn"], h, m, fp8) if moe
                else _swiglu(p["ffn"], h, fp8))


def _nll(h, labels, head, fp8, _):
    logits = _mm("nd,vd->nv", h, head, fp8)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def loss(params, tokens, m: dict, fp8: bool = False):
    """Mean next-token cross-entropy of (b, s + 1) token rows."""
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    emb = params["embed"]
    if fp8:
        emb = emb.astype(F8).astype(F32)
    x = jnp.take(emb, inputs, axis=0)
    for stack, moe in (("dense_blocks", False), ("blocks", True)):
        layer = jax.checkpoint(partial(_layer, m=m, fp8=fp8, moe=moe))
        x, _ = jax.lax.scan(lambda x, p: (layer(p, x), None), x,
                            params[stack])
    h = _norm(x, params["final_norm"]["scale"])
    d = h.shape[-1]
    nll = _chunks(lambda hb, lb, i: _nll(hb, lb, params["lm_head"], fp8, i),
                  CE_CHUNK, h.reshape(-1, d), labels.reshape(-1))
    return jnp.sum(nll) / labels.size


# ---------------------------------------------------------------------------
# model FLOPs and the expert kernels' work
# ---------------------------------------------------------------------------

def routed_rows(m: dict, tokens: int) -> float:
    """Copies a layer's held experts get from `tokens` tokens, expected:
    tokens * experts_per_token * num_experts / router_experts."""
    return tokens * m["experts_per_token"] * m["num_experts"] \
        / m["router_experts"]


def matmul_params(m: dict) -> int:
    """Parameters a token meets in matmuls, the routed experts at their
    expected share: latent attention and the feed-forward of every layer
    (router and shared experts in the expert layers) and the LM head."""
    d, h, nope, rope, vd, r = _widths(m)
    mla = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) \
        + h * vd * d
    expert = 3 * d * m["d_ff"]
    moe = d * m["router_experts"] + 3 * d * m["shared_expert_ff"] \
        + expert * m["experts_per_token"] * m["num_experts"] \
        // m["router_experts"]
    n_dense = m["first_dense_layers"]
    return (n_dense * (mla + 3 * d * m["dense_d_ff"])
            + (m["num_layers"] - n_dense) * (mla + moe) + m["vocab"] * d)


def attention_flops_per_token(m: dict, seq: int) -> float:
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return 3.0 * m["num_layers"] * m["num_heads"] * width * seq


def flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * matmul_params(m) + attention_flops_per_token(m, seq)


# Grouped products a training step runs per expert layer under full remat:
# the three forward ones (gate, up, down) twice, the three input
# cotangents and the three weight cotangents once.
EXPERT_PRODUCTS = 12


def expert_kernel_work(m: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the expert kernels in one step over `tokens`
    tokens, for the expected routed rows. Every product is rows x d_model
    x d_ff (both orientations); its bytes are the least it must move: the
    held experts' bfloat16 matrices once, its rows in and its rows out."""
    rows = routed_rows(m, tokens)
    d, f, e = m["d_model"], m["d_ff"], m["num_experts"]
    n = EXPERT_PRODUCTS * (m["num_layers"] - m["first_dense_layers"])
    return n * 2.0 * rows * d * f, n * 2.0 * (e * d * f + rows * (d + f))
