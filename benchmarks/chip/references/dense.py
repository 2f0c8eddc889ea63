"""Plain reference of the dense transformer: the parameter tree, the loss in
float32 at the highest matmul precision, and the model FLOPs per token.

A configuration file names this model with `"reference": "dense"`. Like
every module of `references/`, it exports `param_shapes(m)`,
`loss(params, tokens, m, fp8=False)` and `flops_per_token(m, seq)`, where
`m` is the file's `model` section, and imports nothing of the program or
of the harness.

Departures from the published models, which the program makes too and
the reference therefore follows: full-dimension RoPE, norm epsilon 1e-6,
no MLP or output-projection biases, and bias-free attention where the
configuration says so.

`fp8=True` computes every matmul (and the embedding lookup) on float8
e4m3 operands: the control, one precision below the bfloat16 the
configuration states.

Model FLOPs (the `mfu` convention): a trained token costs 6 FLOPs per
parameter it meets in a matrix multiplication (2 forward, 4 backward),
plus causal attention: per layer and token, QK^T and PV over on average
seq/2 keys are 2 * 2 * (seq/2) * heads * head_dim FLOPs forward, times 3
with the backward. The embedding lookup, norms, biases, the loss and any
recomputation under rematerialisation are not counted.
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
CE_CHUNK = 512  # positions per block of the loss


def param_shapes(m: dict) -> dict:
    """The parameter tree (names, stacked layer axis, shapes, dtype)."""
    dt = jnp.dtype(m["dtype"])
    L, d, f = m["num_layers"], m["d_model"], m["d_ff"]
    hq, hk = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    vp = -(-m["vocab"] // 16) * 16
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dt)

    def norm(*lead):
        p = {"scale": s(*lead, d)}
        if m["norm"] == "layernorm":
            p["bias"] = s(*lead, d)
        return p

    mixer = {"wq": s(L, d, hq), "wk": s(L, d, hk), "wv": s(L, d, hk),
             "wo": s(L, hq, d)}
    if m["qkv_bias"]:
        mixer.update(bq=s(L, hq), bk=s(L, hk), bv=s(L, hk))
    ffn = {"w_up": s(L, d, f), "w_down": s(L, f, d)}
    if m["act"] == "swiglu":
        ffn["w_gate"] = s(L, d, f)
    return {"embed": s(vp, d), "lm_head": s(vp, d), "final_norm": norm(),
            "blocks": {"ln1": norm(L), "ln2": norm(L), "mixer": mixer,
                       "ffn": ffn}}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _mm(spec, a, b, fp8):
    if fp8:
        return jnp.einsum(spec, a.astype(F8), b.astype(F8),
                          preferred_element_type=F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, p, kind):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x: (b, s, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(x.shape[1])[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), F32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), F32)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, q0, m, fp8):
    """Softmax attention of the query rows [q0, q0 + len) over all keys."""
    s = k.shape[1]
    scores = _mm("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(m["head_dim"])
    qi = q0 + np.arange(q.shape[1])[:, None]
    ki = np.arange(s)[None, :]
    mask = ki <= qi
    if m.get("sliding_window"):
        mask &= qi - ki < m["sliding_window"]
    scores = jnp.where(jnp.asarray(mask)[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return _mm("bhqk,bkhd->bqhd", probs, v, fp8)


def _layer(p, x, m, fp8):
    b, s, d = x.shape
    hd, nh, nk = m["head_dim"], m["num_heads"], m["num_kv_heads"]
    h = _norm(x, p["ln1"], m["norm"])
    mx = p["mixer"]
    q = _mm("bsd,df->bsf", h, mx["wq"], fp8)
    k = _mm("bsd,df->bsf", h, mx["wk"], fp8)
    v = _mm("bsd,df->bsf", h, mx["wv"], fp8)
    if "bq" in mx:
        q, k, v = q + mx["bq"], k + mx["bk"], v + mx["bv"]
    q = _rope(q.reshape(b, s, nh, hd), m["rope_theta"])
    k = _rope(k.reshape(b, s, nk, hd), m["rope_theta"])
    v = v.reshape(b, s, nk, hd)
    rep = nh // nk
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    att = jax.checkpoint(partial(_attend, m=m, fp8=fp8), static_argnums=(3,))
    out = jnp.concatenate(
        [att(q[:, i:i + Q_CHUNK], k, v, i) for i in range(0, s, Q_CHUNK)],
        axis=1)
    x = x + _mm("bsf,fd->bsd", out.reshape(b, s, nh * hd), mx["wo"], fp8)
    h = _norm(x, p["ln2"], m["norm"])
    ff = p["ffn"]
    if m["act"] == "swiglu":
        a = jax.nn.silu(_mm("bsd,df->bsf", h, ff["w_gate"], fp8)) \
            * _mm("bsd,df->bsf", h, ff["w_up"], fp8)
    else:
        a = jax.nn.gelu(_mm("bsd,df->bsf", h, ff["w_up"], fp8),
                        approximate=True)
    return x + _mm("bsf,fd->bsd", a, ff["w_down"], fp8)


def _nll_sum(h, head, labels, vocab, fp8):
    logits = _mm("bsd,vd->bsv", h, head, fp8)[..., :vocab]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def loss(params, tokens, m: dict, fp8: bool = False):
    """Mean next-token cross-entropy of (b, s + 1) token rows."""
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    emb = params["embed"]
    if fp8:
        emb = emb.astype(F8).astype(F32)
    x = jnp.take(emb, inputs, axis=0)
    layer = jax.checkpoint(partial(_layer, m=m, fp8=fp8))
    for i in range(m["num_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], params["blocks"]), x)
    h = _norm(x, params["final_norm"], m["norm"])
    nll = jax.checkpoint(partial(_nll_sum, vocab=m["vocab"], fp8=fp8))
    s = inputs.shape[1]
    total = sum(nll(h[:, i:i + CE_CHUNK], params["lm_head"],
                    labels[:, i:i + CE_CHUNK]) for i in range(0, s, CE_CHUNK))
    return total / labels.size


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def matmul_params(m: dict) -> int:
    """Parameters met in matmuls per token: attention and MLP of every
    layer, and the LM head."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    mlp = (3 if m["act"] == "swiglu" else 2) * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + m["vocab"] * d


def attention_flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * seq


def flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * matmul_params(m) + attention_flops_per_token(m, seq)
