"""Mixture-of-Experts FFN — capacity-free (dropless) top-k routing.

TPU-native dispatch (DESIGN.md §5): tokens stay data-sharded, every expert's
d_ff is tensor-parallel over the "model" axis, and dispatch is a local sort
of the client's token copies + grouped matmuls:

  1. router logits -> top-k experts + their weights per token
  2. argsort the b*s*k token copies by expert id, the held experts first
     (a local sort: the batch of one client lives on its chips); the held
     experts' copies are the first sum(counts) of that order
  3. in chunks of C rows of that order (`chunk_rows`: twice the copies
     the held experts expect, in whole 512-row tiles), as many as the held
     copies need: gather the chunk's token rows, one grouped matmul per
     FFN matrix over the chunk's share of each expert's group
  4. add each row's output times its copy's weight into its token's row
     (a scatter-add in float32)

No array has a row for every copy: the copies of absent experts are never
gathered. The chunk loop's trip count is dynamic, so the layer is a
custom_vjp whose backward pass runs the same chunks, recomputing their
products, and scatters the row cotangents back into the tokens.

Routers (`ArchConfig.router`): "softmax" takes the top k of the softmax
probabilities and renormalises them (qwen2-moe, dbrx). "sigmoid" is
DeepSeek-V3's `noaux_tc` with one group: sigmoid scores over every expert,
the top k chosen by score plus a correction bias that only selects (it
never weights, and no gradient reaches it), the chosen scores renormalised
and times `routed_scaling`.

A layer may hold a share of the experts (model-configs guide §4, expert
parallelism without its exchange): `num_experts` held of the router's
`n_router` outputs, starting at `first_expert`. It routes over all of
them and computes only its own experts' part of the result, for the
copies routed to them; what the absent experts would add is left out.
The copies are sorted with the held experts first, so they are groups
0..num_experts-1 and every other copy lies past the last group.

The grouped matmuls are `lax.ragged_dot_general` off the TPU. On the TPU
they are megablox's Pallas kernels, `gmm` forward and for the input
cotangent and `tgmm` for the weight cotangent, under the jit names
`moe_gmm` and `moe_tgmm` so a device trace can find them; a chunk's
trailing rows past the held copies form one more group, which is never
computed (only the held experts' tiles run) and comes back zero. GSPMD
cannot split them: under a mesh of several chips each chip runs its own
clients' products (a shard_map over the client axes), and a mesh that
splits the experts' widths over "model" is refused.

Qwen2-MoE's 4 shared experts (DeepSeek's 2) are folded into one dense FFN
of width `shared_expert_ff` applied to every token (mathematically
identical to always-routed experts of the same total width).

Note (roofline): on the CPU backend XLA lowers ragged_dot as a dense
group-loop, so `cost_analysis()` FLOPs over-count by ~E/k.
"""
from __future__ import annotations

import functools
import importlib
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import custom_batching, lax
from jax.lax import RaggedDotDimensionNumbers, ragged_dot_general
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.layers import init_mlp, linear, mlp


def init_moe(key, cfg: ArchConfig):
    """Every leaf in the configuration's dtype, the router's too."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, cfg.n_router), cfg.dtype)
        * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, f), cfg.dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), cfg.dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), cfg.dtype) * s_out,
    }
    if cfg.router == "sigmoid":
        p["b_router"] = jnp.zeros((cfg.n_router,), cfg.dtype)
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


# ---------------------------------------------------------------------------
# the TPU's grouped matmuls (megablox, Pallas)
# ---------------------------------------------------------------------------

_MEGABLOX = "jax.experimental.pallas.ops.tpu.megablox.gmm"


def _tile(dim: int, want: int) -> int:
    """`want` where it divides `dim`, else the whole dimension."""
    return want if dim % want == 0 else dim


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(rows, contraction, output) tiles: 512 rows where the rows allow
    (a multiple of 128 otherwise), 512-wide contraction and output tiles
    where they divide (whole up to 1536 wide otherwise: 1408 = 11 * 128).
    At the chip share's widths every kernel's buffers fit the 16 MiB of
    scoped VMEM of a v5e core."""
    tm = 512 if m % 512 == 0 else 128
    return tm, _tile(k, 512), n if n <= 1536 else _tile(n, 512)


@partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def moe_gmm(lhs, rhs, group_sizes, transpose_rhs: bool = False,
            interpret: bool = False):
    """(M, K) rows in groups x (G, K, N) -> (M, N): each of the first G
    groups of `group_sizes` times its matrix; rows of later groups come
    back zero and are never computed."""
    gmm = importlib.import_module(_MEGABLOX).gmm.__wrapped__
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, lhs.dtype,
               _tiling(lhs.shape[0], lhs.shape[1], n),
               transpose_rhs=transpose_rhs, interpret=interpret)


@partial(jax.jit, static_argnames=("num_groups", "interpret"))
def moe_tgmm(lhs_t, g, group_sizes, num_groups: int, interpret: bool = False):
    """(K, M) x (M, N) -> (num_groups, K, N): per group among the first
    `num_groups`, the sum over its rows of lhs^T g."""
    tgmm = importlib.import_module(_MEGABLOX).tgmm.__wrapped__
    return tgmm(lhs_t, g, group_sizes, g.dtype,
                _tiling(g.shape[0], lhs_t.shape[0], g.shape[1]),
                num_actual_groups=num_groups, interpret=interpret)


def _per_member(f):
    """A custom_vmap rule that runs `f` once per batch member, the batch
    being the train step's clients. Under a mesh of several chips each
    chip runs its own clients (a shard_map over the client axes: GSPMD
    cannot split a Pallas kernel); one chip holds one client, so the loop
    runs once."""
    def loop(*args):
        if args[0].shape[0] == 1:
            return f(*(a[0] for a in args))[None]
        return lax.map(lambda a: f(*a), args)

    def rule(axis_size, in_batched, *args):
        args = _batch_all(axis_size, in_batched, *args)
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or mesh.size == 1:
            return loop(*args), True
        clients = P(tuple(a for a in mesh.axis_names if a != "model"))
        return jax.shard_map(loop, mesh=mesh, in_specs=clients,
                             out_specs=clients, check_vma=False)(*args), True
    return rule


def _with_rest(gs, m):
    """The held experts' group sizes, then one group of the other rows."""
    return jnp.concatenate([gs, (m - jnp.sum(gs))[None]]).astype(jnp.int32)


def _vmapped(f):
    op = custom_batching.custom_vmap(f)
    op.def_vmap(_per_member(f))
    return op


@functools.cache
def _kernel_grouped(interpret: bool = False):
    """The Pallas grouped matmul with its backward pass, on (M, K) rows
    sorted by group and the held experts' group sizes."""
    fwd = _vmapped(lambda lhs, rhs, gs: moe_gmm(
        lhs, rhs, _with_rest(gs, lhs.shape[0]), interpret=interpret))
    dlhs = _vmapped(lambda g, rhs, gs: moe_gmm(
        g, rhs, _with_rest(gs, g.shape[0]), transpose_rhs=True,
        interpret=interpret))
    drhs = _vmapped(lambda lhs, g, gs: moe_tgmm(
        lhs.T, g, _with_rest(gs, g.shape[0]), gs.shape[-1],
        interpret=interpret))

    @jax.custom_vjp
    def grouped(lhs, rhs, gs):
        return fwd(lhs, rhs, gs)

    def grouped_fwd(lhs, rhs, gs):
        return fwd(lhs, rhs, gs), (lhs, rhs, gs)

    def grouped_bwd(res, g):
        lhs, rhs, gs = res
        g = g.astype(lhs.dtype)
        return dlhs(g, rhs, gs), drhs(lhs, g, gs).astype(rhs.dtype), None

    grouped.defvjp(grouped_fwd, grouped_bwd)
    return grouped


def _ragged_grouped(lhs, rhs, gs):
    """The same product off the TPU, as ragged_dot: the rows past the
    held groups form one more group, of a zero matrix (so that every row
    lies in a group when the client axis folds into the groups)."""
    rest = jnp.zeros((1,) + rhs.shape[1:], rhs.dtype)
    sizes = _with_rest(gs, lhs.shape[0])
    return _ragged(lhs[None], jnp.concatenate([rhs, rest]), sizes[None])[0]


def _whole_experts() -> bool:
    """No "model" axis of the traced program's mesh splits the experts'
    widths (DESIGN.md §5 shards their d_ff over it)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh.empty or dict(mesh.shape).get("model", 1) == 1


def grouped_matmul(lhs, rhs, group_sizes, *, kernel: bool | None = None,
                   interpret: bool = False):
    """lhs (M, K) rows sorted by group, rhs (G, K, N), group_sizes (G,) ->
    (M, N); rows past the G groups come back zero. `kernel` (default: on
    a TPU, for whole 128-row tiles) picks the Pallas kernels over
    ragged_dot. The kernels need each chip to hold its experts whole."""
    if kernel is None:
        kernel = jax.default_backend() == "tpu" and lhs.shape[0] % 128 == 0
    if kernel:
        if not _whole_experts():
            raise NotImplementedError(
                "the expert layer's Pallas kernels need whole experts on "
                "every chip; this mesh splits their d_ff over 'model' "
                "(DESIGN.md §5)")
        return _kernel_grouped(interpret)(lhs, rhs, group_sizes)
    return _ragged_grouped(lhs, rhs, group_sizes)


# ---------------------------------------------------------------------------
# routing and the layer
# ---------------------------------------------------------------------------

def route(p, x, cfg: ArchConfig):
    """x (..., D) -> (weights (..., k) f32, experts (..., k) int32, the
    scores over every expert (..., n_router) f32)."""
    k = cfg.experts_per_token
    logits = linear(x.astype(jnp.float32), p["router"].astype(jnp.float32))
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = lax.stop_gradient(p["b_router"].astype(jnp.float32))
        _, top_e = lax.top_k(scores + bias, k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
        return top_w * cfg.routed_scaling, top_e, scores
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return top_w, top_e, probs


def chunk_rows(copies: int, held: int, n_router: int) -> int:
    """C, the rows of one chunk of the held experts' copies: twice the
    copies they expect (`copies` * held / n_router) in whole 512-row
    tiles, and no more than every copy. A layer that holds every expert
    takes all its copies in one chunk."""
    return min(copies, 512 * math.ceil(2 * copies * held / n_router / 512))


def _chunk(order, counts, i, c: int, k: int):
    """Sorted rows i*c .. i*c + c - 1: (copy ids, their tokens, the held
    experts' group sizes among them). Rows past the held copies, or past
    the last copy, lie in no group, so their products come back zero."""
    lo = i * c
    copies = jnp.take(order, lo + jnp.arange(c), mode="clip")
    ends = jnp.cumsum(counts)
    gs = jnp.clip(jnp.minimum(ends, lo + c) - jnp.maximum(ends - counts, lo),
                  0)
    return copies, copies // k, gs.astype(jnp.int32)


def _expert_ffn(xs, w_gate, w_up, w_down, gs, act: str):
    """The held experts' FFN on rows sorted by expert, `gs` rows each;
    rows past the groups come back zero."""
    mm = partial(grouped_matmul, group_sizes=gs)
    if act == "swiglu":
        h = jax.nn.silu(mm(xs, w_gate)) * mm(xs, w_up)
    else:
        h = jax.nn.gelu(mm(xs, w_up))
    return mm(h, w_down)


@custom_batching.custom_vmap
def _longest(n):
    """n; under vmap the most over the batch, unbatched. The chunk loop
    then runs as often as its longest member needs, with no select over
    its carry: a member past its own chunks adds nothing."""
    return n


@_longest.def_vmap
def _longest_vmap(axis_size, in_batched, n):
    return (jnp.max(n) if in_batched[0] else n), False


@functools.cache
def _held_experts(c: int, k: int, act: str):
    """(x (T, D), w_gate, w_up, w_down, w (T*k,) f32, order, counts) ->
    (T, D): the sum over each token's copies routed to a held expert of
    its weight times that expert's output. The copies sorted by `order`
    run in chunks of `c` rows, as many as the held copies (sum(counts))
    need; the backward pass recomputes each chunk's products."""

    def contrib(xs, w_gate, w_up, w_down, wt, gs):
        with jax.named_scope("moe_experts"):
            ys = _expert_ffn(xs, w_gate, w_up, w_down, gs, act)
        return ys.astype(jnp.float32) * wt[:, None]

    def loop(body, counts, init):
        chunks = _longest((jnp.sum(counts) + c - 1) // c)
        return lax.fori_loop(0, chunks, body, init)

    def forward(x, w_gate, w_up, w_down, w, order, counts):
        def body(i, acc):
            with jax.named_scope("moe_dispatch"):
                copies, tok, gs = _chunk(order, counts, i, c, k)
                xs, wt = x[tok], w[copies]
            y = contrib(xs, w_gate, w_up, w_down, wt, gs)
            with jax.named_scope("moe_dispatch"):
                return acc.at[tok].add(y)
        acc = loop(body, counts, jnp.zeros(x.shape, jnp.float32))
        return acc.astype(x.dtype)

    def fwd(*res):
        return forward(*res), res

    def bwd(res, dy):
        x, w_gate, w_up, w_down, w, order, counts = res

        def body(i, acc):
            dx, dgate, dup, ddown, dw = acc
            with jax.named_scope("moe_dispatch"):
                copies, tok, gs = _chunk(order, counts, i, c, k)
                xs, wt, dys = x[tok], w[copies], dy[tok]
            _, vjp = jax.vjp(
                lambda *a: contrib(*a, gs), xs, w_gate, w_up, w_down, wt)
            dxs, g_gate, g_up, g_down, dwt = vjp(dys.astype(jnp.float32))
            with jax.named_scope("moe_dispatch"):
                return (dx.at[tok].add(dxs.astype(jnp.float32)),
                        dgate + g_gate, dup + g_up, ddown + g_down,
                        dw.at[copies].add(dwt))

        f32 = lambda a: jnp.zeros(a.shape, jnp.float32)
        dx, dgate, dup, ddown, dw = loop(
            body, counts, tuple(map(f32, (x, w_gate, w_up, w_down, w))))
        return (dx.astype(x.dtype), dgate.astype(w_gate.dtype),
                dup.astype(w_up.dtype), ddown.astype(w_down.dtype), dw,
                None, None)

    held_experts = jax.custom_vjp(forward)
    held_experts.defvjp(fwd, bwd)
    return held_experts


def moe_routed(p, x, cfg: ArchConfig, *, first_expert: int = 0):
    """The held experts' part of the routed result: x (B, S, D) -> (y
    (B, S, D), stats). The layer holds experts first_expert ..
    first_expert + num_experts - 1 of the router's n_router. stats:
    `local_rows`, the copies routed to the held experts,
    `max_expert_rows`, the most any one of them got, and
    `expert_chunks`, the chunks of `chunk_rows` rows they ran in."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    c = chunk_rows(b * s * k, e, cfg.n_router)
    with jax.named_scope("moe_router"):
        top_w, top_e, _ = route(p, x, cfg)
    with jax.named_scope("moe_dispatch"):
        # held experts first: copy ids relative to the first held expert
        flat_e = (top_e.reshape(b * s * k) - first_expert) % cfg.n_router
        order = jnp.argsort(flat_e)
        counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=0)
    y = _held_experts(c, k, cfg.act)(
        x.reshape(b * s, d), p["w_gate"], p["w_up"], p["w_down"],
        top_w.reshape(b * s * k), order, counts)
    local = jnp.sum(counts)
    return y.reshape(b, s, d), {"local_rows": local,
                                "max_expert_rows": jnp.max(counts),
                                "expert_chunks": (local + c - 1) // c}


def moe_ffn(p, x, cfg: ArchConfig, *, return_aux: bool = False,
            with_stats: bool = False):
    """x: (B, S, D) -> (B, S, D): the held experts' part plus the shared
    experts. Works for S == 1 (decode) unchanged. `with_stats` returns
    (y, stats) (`moe_routed`)."""
    y, stats = moe_routed(p, x, cfg)
    if cfg.shared_expert_ff:
        with jax.named_scope("moe_shared"):
            y = y + mlp(x, p["shared"], cfg.act)

    if return_aux:
        # Switch-style load-balance diagnostics (fraction routed per expert
        # vs mean router prob) — exposed to the training loop for logging.
        _, top_e, probs = route(p, x, cfg)
        frac = jnp.mean(
            jax.nn.one_hot(top_e, cfg.n_router, dtype=jnp.float32),
            axis=(0, 1, 2))
        mean_p = jnp.mean(probs, axis=(0, 1))
        aux = cfg.n_router * jnp.sum(frac * mean_p)
        return y, aux
    if with_stats:
        return y, stats
    return y


def moe_ffn_ref(p, x, cfg: ArchConfig):
    """Dense-einsum oracle (every held expert for every token, masked
    sum) — used by tests to validate the grouped dispatch."""
    e = cfg.num_experts
    top_w, top_e, _ = route(p, x, cfg)
    # (B, S, E) combine weights of the held experts
    comb = jnp.sum(jax.nn.one_hot(top_e, cfg.n_router)[..., :e]
                   * top_w[..., None], axis=2)
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
