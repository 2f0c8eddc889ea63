"""Plain reference of what a training cell computes: the configuration's
model (its module under `references/`, which `cell.reference_model`
loads by the name the configuration file gives), the DIANA wire over a
shared Rand-block window, and the SGD update of parameters stored in the
configuration's dtype. The wire and the update work over any parameter
tree; only the model varies with the configuration.

It imports nothing of the program. What it shares with the run is the
benchmark's own inputs: the seeded weights (`weights.py`), the token rows
fed to each round, and the round key.

The wire, per parameter leaf in flattening order i, round t (from 0):
the leaf's rows are all its axes but the last, zero-padded to a multiple
of 8; nb = rows / 8 blocks, kb = max(1, int(fraction * nb)); the window is
kb consecutive blocks (mod nb) from randint(fold_in(fold_in(key, t), i),
0, nb), shared by all clients. Q(x) keeps the window scaled by nb / kb.
DIANA: direction = H + mean_m Q(g_m - h_m), h_m += alpha Q(g_m - h_m),
H += beta mean_m Q(g_m - h_m), alpha = fraction, beta = alpha * m / C.
SGD: p <- round_to_dtype(p - lr * direction).

`fp8=True` runs the model's control (every matmul on float8 e4m3
operands); `half_batch=True` leaves out the second half of each client's
rows: one of the faults the comparison must catch.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights

F32 = jnp.float32
BLOCK_ROWS = 8


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


def run(model, m: dict, wkey, rkey, feeds, *, fraction, lr, alpha, beta,
        fresh_clients: bool, fp8: bool = False, half_batch: bool = False):
    """Follow the program's first rounds; return the readings compared.

    model: the configuration's reference model module; m: the file's
    `model` section. feeds: per round, an (m_clients, b, s + 1) int array of the rows each
    client trained on. fresh_clients: every round's cohort is new to the
    wire (a fleet's first rounds under cohort-RR), so its shifts start at 0;
    otherwise the same clients return every round.
    """
    shapes = model.param_shapes(m)
    dtype = jnp.dtype(m["dtype"])
    wire = Wire(shapes, fraction, lr, dtype)
    params = jax.jit(lambda k: weights.make_params(k, shapes, F32))(wkey)
    vg = jax.jit(jax.value_and_grad(partial(model.loss, m=m, fp8=fp8)))
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
