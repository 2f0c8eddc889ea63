"""Plain reference of what a training cell computes: the dense transformer's
loss and gradients in float32 at the highest matmul precision, the DIANA
wire over a shared Rand-block window, and the SGD update of parameters
stored in the configuration's dtype.

It imports nothing of the program. What it shares with the run is the
benchmark's own inputs: the seeded weights (`weights.py`), the token rows
fed to each round, and the round key. Departures from the published
models, which the program makes too and the reference therefore follows:
full-dimension RoPE, norm epsilon 1e-6, no MLP or output-projection
biases, and bias-free attention where the configuration says so.

The wire, per parameter leaf in flattening order i, round t (from 0):
the leaf's rows are all its axes but the last, zero-padded to a multiple
of 8; nb = rows / 8 blocks, kb = max(1, int(fraction * nb)); the window is
kb consecutive blocks (mod nb) from randint(fold_in(fold_in(key, t), i),
0, nb), shared by all clients. Q(x) keeps the window scaled by nb / kb.
DIANA: direction = H + mean_m Q(g_m - h_m), h_m += alpha Q(g_m - h_m),
H += beta mean_m Q(g_m - h_m), alpha = fraction, beta = alpha * m / C.
SGD: p <- round_to_dtype(p - lr * direction).

`fp8=True` computes every matmul (and the embedding lookup) on float8
e4m3 operands: the control, one precision below the bfloat16 the
configuration states. `half_batch=True` leaves out the second half of each
client's rows: one of the faults the comparison must catch.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights

F32 = jnp.float32
F8 = jnp.float8_e4m3fn
HIGHEST = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 8
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
# the wire and the update
# ---------------------------------------------------------------------------

def geometry(shape, fraction):
    rows = math.prod(shape[:-1]) if len(shape) >= 2 else shape[0]
    cols = shape[-1] if len(shape) >= 2 else 1
    rows_p = rows + (-rows) % BLOCK_ROWS
    nb = rows_p // BLOCK_ROWS
    return rows, cols, rows_p, nb, max(1, int(fraction * nb))


def _rows(x, rows_p):
    r = x.reshape(-1, x.shape[-1]) if x.ndim >= 2 else x.reshape(-1, 1)
    return jnp.pad(r, ((0, rows_p - r.shape[0]), (0, 0)))


class Wire:
    """Leaf geometry and the jitted pieces of one cell's reference."""

    def __init__(self, shapes, fraction, lr, dtype):
        leaves = jax.tree.leaves(shapes)
        self.treedef = jax.tree.structure(shapes)
        self.geo = [geometry(a.shape, fraction) for a in leaves]
        self.shapes = [a.shape for a in leaves]
        nbs = [g[3] for g in self.geo]

        @jax.jit
        def starts(key, t):
            rk = jax.random.fold_in(key, t)
            return jnp.stack([jax.random.randint(jax.random.fold_in(rk, i),
                                                 (), 0, nb)
                              for i, nb in enumerate(nbs)])

        @jax.jit
        def windows(grads, st):
            out = []
            for g, (_, _, rows_p, nb, kb), s in zip(
                    jax.tree.leaves(grads), self.geo, st):
                blocks = _rows(g, rows_p).reshape(nb, BLOCK_ROWS, -1)
                out.append(blocks[(s + jnp.arange(kb)) % nb].reshape(
                    kb * BLOCK_ROWS, -1))
            return out

        @jax.jit
        def norms(tree):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                              for x in jax.tree.leaves(tree)])

        @jax.jit
        def apply(params, wins):
            """p <- round(p - lr * sum of the windows' scattered values)."""
            out = []
            for p, (rows, cols, rows_p, _, _), shape, ws in zip(
                    jax.tree.leaves(params), self.geo, self.shapes, wins):
                d = jnp.zeros((rows_p, cols), F32)
                for idx, vals in ws:
                    d = d.at[idx].add(vals)
                d = d[:rows].reshape(shape)
                out.append(weights.rounded(p - lr * d, dtype))
            return jax.tree.unflatten(self.treedef, out)

        self.starts, self.windows, self.norms, self.apply = (
            starts, windows, norms, apply)

    def row_index(self, i, start):
        _, _, _, nb, kb = self.geo[i]
        blocks = (start + np.arange(kb)) % nb
        return (blocks[:, None] * BLOCK_ROWS
                + np.arange(BLOCK_ROWS)[None]).reshape(-1).astype(np.int32)


def run(m: dict, wkey, rkey, feeds, *, fraction, lr, alpha, beta,
        fresh_clients: bool, fp8: bool = False, half_batch: bool = False):
    """Follow the program's first rounds; return the readings compared.

    feeds: per round, an (m_clients, b, s + 1) int array of the rows each
    client trained on. fresh_clients: every round's cohort is new to the
    wire (a fleet's first rounds under cohort-RR), so its shifts start at 0;
    otherwise the same clients return every round.
    """
    shapes = param_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    wire = Wire(shapes, fraction, lr, dtype)
    params = jax.jit(lambda k: weights.make_params(k, shapes, F32))(wkey)
    vg = jax.jit(jax.value_and_grad(partial(loss, m=m, fp8=fp8)))
    n_leaves = len(wire.geo)
    h = {}  # client -> per-leaf padded host rows (zero pages until written)
    hwin = [[] for _ in range(n_leaves)]  # H as (row index, values) windows
    out = {"loss": [], "shift": []}
    for t, feed in enumerate(feeds):
        feed = np.asarray(feed)
        if half_batch:
            feed = feed[:, :max(1, feed.shape[1] // 2)]
        st = np.asarray(wire.starts(rkey, t))
        idx = [wire.row_index(i, s) for i, s in enumerate(st)]
        vals, losses = [], []
        for c in range(feed.shape[0]):
            lval, g = vg(params, jnp.asarray(feed[c]))
            losses.append(float(lval))
            if t == 0:
                gn = np.asarray(wire.norms(g))
                out["gnorm"] = gn if c == 0 else np.maximum(out["gnorm"], gn)
            win = [np.asarray(w) for w in wire.windows(g, jnp.asarray(st))]
            del g
            client = (t, c) if fresh_clients else c
            hc = h.setdefault(client, [np.zeros((geo[2], geo[1]), np.float32)
                                       for geo in wire.geo])
            v = []
            for i in range(n_leaves):
                _, _, _, nb, kb = wire.geo[i]
                v.append((win[i] - hc[i][idx[i]]) * np.float32(nb / kb))
            vals.append(v)
        qm = [np.mean([v[i] for v in vals], axis=0) for i in range(n_leaves)]
        for c, v in enumerate(vals):
            client = (t, c) if fresh_clients else c
            hc = h[client]
            for i in range(n_leaves):
                hc[i][idx[i]] += np.float32(alpha) * v[i]
            if fresh_clients or t == 0:
                out["shift"].append(
                    [float(np.linalg.norm(hc[i][idx[i]]))
                     for i in range(n_leaves)])
        if t == 0:
            out["grad"] = [float(np.linalg.norm(q)) for q in qm]
        wins = [[(jnp.asarray(ix), jnp.asarray(vv)) for ix, vv in hwin[i]]
                + [(jnp.asarray(idx[i]), jnp.asarray(qm[i]))]
                for i in range(n_leaves)]
        params = wire.apply(params, wins)
        for i in range(n_leaves):
            hwin[i].append((idx[i], np.float32(beta) * qm[i]))
        out["loss"].append(float(np.mean(losses)))
    out["change"] = [float(x) for x in np.asarray(
        weights.init_distance(wkey, params, shapes))]
    out["gnorm"] = [float(x) for x in out["gnorm"]]
    return out
