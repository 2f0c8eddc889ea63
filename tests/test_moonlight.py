"""Moonlight-16B-A3B's mechanisms against the plain float32 reference
(`benchmarks/chip/references/moonlight.py`) on seeded weights at a small
size: latent attention and its cache, the sigmoid router with its
selection-only bias, the expert layer's shares, the whole model's loss and
gradients, and the Pallas grouped matmul (interpret mode) against
ragged_dot.

The configuration is `reduced()` Moonlight in float32 (the program's
attention still feeds its probabilities to the value product in bfloat16,
which sets the tolerances), with a 512-id vocabulary as the reference
keeps it unpadded.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_chip_config, get_config, reduced
from repro.models import mixers, moe
from repro.models import transformer as T

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "reference_moonlight", ROOT / "benchmarks/chip/references/moonlight.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

KEYS = ("num_layers", "d_model", "num_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff", "vocab",
        "num_experts", "router_experts", "experts_per_token",
        "shared_expert_ff", "routed_scaling", "first_dense_layers",
        "dense_d_ff", "rope_theta")
CFG = dataclasses.replace(reduced(get_config("moonlight-16b-a3b")),
                          vocab=512, dtype=jnp.float32)
M = {**{k: getattr(CFG, k) for k in KEYS}, "dtype": "float32"}
S = 32


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def params():
    p = T.init_params(jax.random.key(0), CFG)
    # a selection bias of the size a trained router carries
    ffn = p["blocks"]["ffn"]
    ffn["b_router"] = 0.05 * jax.random.normal(jax.random.key(9),
                                               ffn["b_router"].shape)
    return p


def tokens(b=2, s=S, seed=1):
    return jax.random.randint(jax.random.key(seed), (b, s + 1), 0, CFG.vocab)


def test_reduced_moonlight_keeps_its_mechanisms():
    assert CFG.attention_mixer == "mla" and CFG.router == "sigmoid"
    assert (CFG.first_dense_layers, CFG.num_experts, CFG.n_router) == (1, 4, 8)
    chip = get_chip_config("moonlight-16b-a3b")
    assert (chip.num_layers, chip.num_experts, chip.n_router, chip.vocab,
            chip.experts_per_token) == (6, 8, 64, 20480, 6)


def test_tree_is_the_references(params):
    ref = REF.param_shapes(M)
    assert jax.tree.structure(params) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        assert a.shape == b.shape


def test_mla_matches_reference(params):
    p = jax.tree.map(lambda a: a[0], params["blocks"])["mixer"]
    x = jax.random.normal(jax.random.key(2), (2, S, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    got = mixers.mla_train(p, x, CFG, positions=pos)
    want = REF._mla(p, x, M, False)
    assert rel(got, want) < 5e-3


def test_mla_prefill_then_latent_decode_matches_train(params):
    """Prefill half the rows, then decode the rest one by one through the
    latent cache (the key and value expansions absorbed into the query and
    the output): each position's output is the full pass's."""
    p = jax.tree.map(lambda a: a[0], params["blocks"])["mixer"]
    x = jax.random.normal(jax.random.key(11), (2, S, CFG.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    full = mixers.mla_train(p, x, CFG, positions=pos)
    half = S // 2
    y, cache = mixers.mla_prefill(p, x[:, :half], CFG,
                                  positions=pos[:, :half], cache_len=S)
    assert cache.ckv.shape == (2, S, CFG.kv_lora_rank)
    assert cache.kpe.shape == (2, S, CFG.qk_rope_head_dim)
    assert rel(y, full[:, :half]) < 1e-4
    decode = jax.jit(lambda c, t, i: mixers.mla_decode(p, t, CFG, c, i))
    for i in range(half, S):
        y, cache = decode(cache, x[:, i:i + 1], jnp.int32(i))
        assert rel(y[:, 0], full[:, i]) < 5e-3, i
    # the model's caches sit under the names of its two layer stacks
    _, caches = T.prefill(params, {"tokens": tokens()[:, :half]}, CFG,
                          cache_len=S)
    assert set(caches) == {"dense_blocks", "blocks"}
    assert caches["blocks"]["mixer"].ckv.shape == (1, 2, S,
                                                   CFG.kv_lora_rank)


def test_sigmoid_router_bias_chooses_but_does_not_weight():
    cfg = CFG
    d, e = cfg.d_model, cfg.n_router
    p = {"router": jax.random.normal(jax.random.key(3), (d, e)) / d ** 0.5,
         "b_router": jnp.zeros((e,))}
    x = jax.random.normal(jax.random.key(4), (64, d))
    w0, e0, scores = moe.route(p, x, cfg)
    # weights: the chosen scores over their sum, times the scaling factor
    chosen = jnp.take_along_axis(scores, e0, axis=-1)
    np.testing.assert_allclose(
        w0, chosen / chosen.sum(-1, keepdims=True) * 2.446, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(-1), 2.446, rtol=1e-6)
    # a bias that favours expert 5 makes every token choose it, with the
    # weight of its own unbiased score
    w1, e1, scores1 = moe.route({**p, "b_router": p["b_router"].at[5].set(
        10.0)}, x, cfg)
    np.testing.assert_array_equal(scores1, scores)
    assert bool(jnp.all(jnp.any(e1 == 5, axis=-1)))
    assert bool(jnp.any(e0 != e1))
    c1 = jnp.take_along_axis(scores, e1, axis=-1)
    np.testing.assert_allclose(
        w1, c1 / c1.sum(-1, keepdims=True) * 2.446, rtol=1e-6)
    # and no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(moe.route(
        {**p, "b_router": b}, x, cfg)[0] ** 2))(p["b_router"])
    np.testing.assert_array_equal(g, 0.0)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Eight chips each holding one of the router's 8 experts: their
    routed parts, plus the shared experts counted once, are the layer with
    every expert held — and the reference's."""
    whole = dataclasses.replace(CFG, num_experts=8, router_experts=8)
    p = moe.init_moe(jax.random.key(5), whole)
    p["b_router"] = 0.05 * jax.random.normal(jax.random.key(6), (8,))
    x = jax.random.normal(jax.random.key(7), (2, 16, CFG.d_model))
    one = dataclasses.replace(whole, num_experts=1)
    parts = 0.0
    for j in range(8):
        share = {**p, **{k: p[k][j:j + 1] for k in ("w_gate", "w_up",
                                                     "w_down")}}
        y, stats = moe.moe_routed(share, x, one, first_expert=j)
        parts = parts + y
    shared = parts + moe.mlp(x, p["shared"], CFG.act)
    uncut = moe.moe_ffn(p, x, whole)
    np.testing.assert_allclose(shared, uncut, rtol=1e-4, atol=1e-5)
    m = {**M, "num_experts": 8, "router_experts": 8}
    assert rel(uncut, REF._moe(p, x, m, False)) < 1e-4
    # one expert of eight holds none of a token's copies but its own
    y, stats = moe.moe_routed(p, x, whole)
    assert int(stats["local_rows"]) == 2 * 16 * CFG.experts_per_token


def test_held_share_is_the_references(params):
    p = jax.tree.map(lambda a: a[0], params["blocks"])["ffn"]
    x = jax.random.normal(jax.random.key(8), (2, S, CFG.d_model))
    got, stats = moe.moe_ffn(p, x, CFG, with_stats=True)
    assert rel(got, REF._moe(p, x, M, False)) < 1e-4
    _, top, _ = moe.route(p, x, CFG)
    assert int(stats["local_rows"]) == int(jnp.sum(top < CFG.num_experts))
    assert int(stats["max_expert_rows"]) == int(jnp.max(jnp.sum(
        jax.nn.one_hot(top, CFG.n_router)[..., :CFG.num_experts],
        axis=(0, 1, 2))))


# 2 of the router's 16 experts held: 2 x 384 tokens x 2 copies = 1536
# copies, 192 expected on the held experts, so a chunk is 512 rows
SPARSE = dataclasses.replace(CFG, num_experts=2, router_experts=16)
QWEN = dataclasses.replace(reduced(get_config("qwen2-moe-a2.7b")),
                           dtype=jnp.float32)


@pytest.mark.parametrize("cfg,favoured", [
    (SPARSE, ()),       # the expected load: one chunk
    (SPARSE, (0,)),     # every token picks expert 0: two or three chunks
    (SPARSE, (0, 1)),   # every copy held: three full chunks
    (QWEN, ()),         # every expert held: one chunk of every copy
], ids=["expected", "one-favoured", "all-held-copies", "qwen2-moe"])
def test_chunked_experts_match_the_dense_reference(cfg, favoured):
    """The held experts run on chunks of the sorted copies routed to them:
    the output and the gradients of x, the router and the three expert
    stacks are the dense oracle's, and `expert_chunks` counts the chunks
    the held copies need."""
    b, s = 2, 384
    p = moe.init_moe(jax.random.key(12), cfg)
    if "b_router" in p:
        p["b_router"] = p["b_router"].at[jnp.array(favoured, int)].set(10.0)
    x = jax.random.normal(jax.random.key(13), (b, s, cfg.d_model))
    cot = jax.random.normal(jax.random.key(14), x.shape)
    c = moe.chunk_rows(b * s * cfg.experts_per_token, cfg.num_experts,
                       cfg.n_router)
    assert c == (512 if cfg is SPARSE else b * s * cfg.experts_per_token)

    def run(f):
        def loss(p, x):
            return jnp.sum(f(p, x) * cot)
        y = f(p, x)
        gp, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)
        return y, gx, {k: gp[k] for k in ("router", "w_gate", "w_up",
                                          "w_down")}

    y, gx, gp = run(jax.jit(lambda p, x: moe.moe_ffn(p, x, cfg)))
    y_ref, gx_ref, gp_ref = run(lambda p, x: moe.moe_ffn_ref(p, x, cfg))
    assert rel(y, y_ref) < 1e-5
    assert rel(gx, gx_ref) < 1e-5
    for k in gp:
        assert rel(gp[k], gp_ref[k]) < 1e-5, k
    _, stats = moe.moe_ffn(p, x, cfg, with_stats=True)
    _, top, _ = moe.route(p, x, cfg)
    held = int(jnp.sum(top < cfg.num_experts))
    assert int(stats["local_rows"]) == held
    assert int(stats["expert_chunks"]) == -(-held // c)
    want = {(): 1, (0,): (2, 3), (0, 1): 3}[favoured]
    assert int(stats["expert_chunks"]) in np.atleast_1d(want)


def test_loss_and_grads_match_reference(params):
    toks = tokens()
    lp, gp = jax.jit(jax.value_and_grad(
        lambda p: T.loss_fn(p, {"tokens": toks}, CFG)))(params)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, toks, M)))(params)
    assert abs(float(lp) - float(lr)) < 1e-3 * float(lr)
    # each leaf's error over the larger of its norm and the median leaf's,
    # as the chip comparison scales it: the router's small gradient sums
    # many cancelling terms, and a near-tie choice that flips between the
    # two attention paths moves it by a token's whole share
    norms = [float(jnp.linalg.norm(b)) for b in jax.tree.leaves(gr)]
    med = float(np.median([n for n in norms if n > 0]))
    worst = max(float(jnp.linalg.norm(a - b)) / max(n, med)
                for a, b, n in zip(jax.tree.leaves(gp), jax.tree.leaves(gr),
                                   norms))
    assert worst < 3e-2
    np.testing.assert_array_equal(gp["blocks"]["ffn"]["b_router"], 0.0)
    _, stats = T.loss_fn(params, {"tokens": toks}, CFG, with_stats=True)
    assert 0 < int(stats["moe_local_rows"]) <= 2 * S * CFG.experts_per_token


def _grouped_case():
    k1, k2, k3 = jax.random.split(jax.random.key(10), 3)
    lhs = jax.random.normal(k1, (256, 128), jnp.float32)
    rhs = jax.random.normal(k2, (3, 128, 256), jnp.float32) / 8
    # 40 rows past the three groups: another chip's experts' copies
    gs = jnp.array([100, 0, 116], jnp.int32)
    cot = jax.random.normal(k3, (256, 256), jnp.float32)
    return lhs, rhs, gs, cot


@pytest.mark.parametrize("members", [1, 2])
def test_pallas_grouped_matmul_matches_ragged_dot(members):
    """megablox's gmm/tgmm (interpret mode) against ragged_dot: forward,
    both cotangents, rows past the held groups zero, under a client vmap
    of one member (the chip's) and of two."""
    lhs, rhs, gs, cot = _grouped_case()
    lhs_b, rhs_b = jnp.stack([lhs] * members), jnp.stack([rhs] * members)

    def run(kernel):
        f = lambda l, r: moe.grouped_matmul(l, r, gs, kernel=kernel,
                                            interpret=True)
        def both(l, r, c):
            out, vjp = jax.vjp(jax.vmap(f), l, r)
            return (out, *vjp(c))
        return jax.jit(both)(lhs_b, rhs_b, jnp.stack([cot] * members))

    got, want = run(True), run(False)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-4)
    np.testing.assert_array_equal(got[0][:, 216:], 0.0)
    np.testing.assert_array_equal(got[1][:, 216:], 0.0)


@pytest.mark.parametrize("members", [2, 4])
def test_pallas_grouped_matmul_runs_per_chip_under_a_mesh(members):
    """Clients split over two chips: each chip runs the kernels (interpret
    mode) for its own clients, one or two each, and the result is
    ragged_dot's."""
    lhs, rhs, gs, cot = _grouped_case()
    # every client's rows, experts and cotangent its own
    args = [jnp.stack([x * (1 + i / 4) for i in range(members)])
            for x in (lhs, rhs, cot)]

    def both(kernel):
        f = lambda l, r: moe.grouped_matmul(l, r, gs, kernel=kernel,
                                            interpret=True)
        def run(l, r, c):
            out, vjp = jax.vjp(jax.vmap(f), l, r)
            return (out, *vjp(c))
        return jax.jit(run)

    want = both(False)(*args)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1),
                ("data", "model"))
    with jax.set_mesh(mesh):
        got = both(True)(*[jax.device_put(x, NamedSharding(mesh, P("data")))
                           for x in args])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-4)


def test_kernel_path_through_the_training_loss(params, monkeypatch):
    """The layer's loss and gradients with the Pallas kernels (interpret
    mode) in place of ragged_dot, under the train step's client vmap."""
    toks = tokens(b=2, s=S)[None]  # one client: 2 x 32 x 2 = 128 copies
    loss = lambda p, t: T.loss_fn(p, {"tokens": t}, CFG)
    grads = jax.jit(jax.vmap(jax.value_and_grad(loss), in_axes=(None, 0)))
    want = grads(params, toks)
    orig = moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda l, r, group_sizes,
                        kernel=None, interpret=False: orig(
                            l, r, group_sizes, kernel=True, interpret=True))
    got = jax.jit(jax.vmap(jax.value_and_grad(loss), in_axes=(None, 0)))(
        params, toks)
    # the kernels and ragged_dot sum in different orders
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_chunked_experts_kernel_path_matches_ragged_dot(monkeypatch):
    """The Pallas kernels (interpret mode) on chunks that start inside a
    group and end past the held copies, under the train step's client
    vmap: the layer's output and gradients are ragged_dot's."""
    p = moe.init_moe(jax.random.key(12), SPARSE)
    p["b_router"] = p["b_router"].at[0].set(10.0)
    x = jax.random.normal(jax.random.key(13), (1, 2, 384, SPARSE.d_model))
    cot = jax.random.normal(jax.random.key(14), x.shape[1:])

    def run():
        loss = lambda p, x: jnp.sum(moe.moe_ffn(p, x, SPARSE) * cot)
        return jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1)),
                                in_axes=(None, 0)))(p, x)

    want = run()
    orig = moe.grouped_matmul
    monkeypatch.setattr(moe, "grouped_matmul", lambda l, r, group_sizes,
                        kernel=None, interpret=False: orig(
                            l, r, group_sizes, kernel=True, interpret=True))
    got = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def test_chunked_experts_clients_need_different_chunk_counts():
    """Under the train step's client vmap the chunk loop runs as often as
    its longest client needs: a client whose copies fit one chunk, beside
    one that needs three, gets its own result and gradients."""
    p = moe.init_moe(jax.random.key(12), SPARSE)
    favoured = p["b_router"].at[:2].set(10.0)
    ps = {**jax.tree.map(lambda a: jnp.stack([a, a]), p),
          "b_router": jnp.stack([p["b_router"], favoured])}
    x = jax.random.normal(jax.random.key(13), (2, 2, 384, SPARSE.d_model))

    def run(p, x):
        (y, stats), vjp = jax.vjp(
            lambda p, x: moe.moe_ffn(p, x, SPARSE, with_stats=True), p, x)
        return y, stats["expert_chunks"], vjp((x, jax.tree.map(
            jnp.zeros_like, stats)))

    batched = jax.jit(jax.vmap(run))(ps, x)
    assert batched[1].tolist() == [1, 3]
    for i in range(2):
        one = jax.jit(run)(jax.tree.map(lambda a: a[i], ps), x[i])
        for a, b in zip(jax.tree.leaves(batched), jax.tree.leaves(one)):
            np.testing.assert_allclose(a[i], b, rtol=1e-5, atol=1e-4)
