"""Per-kernel shape/dtype sweeps: pallas_call (interpret on CPU) vs ref.py,
plus backend-level parity (backend="reference" vs backend="pallas") and the
statistical guarantees (unbiasedness) of the sort-free Rand-k sampler.

Promoted from the ad-hoc parity prints in benchmarks/run.py `[kernels]`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.backend import (
    CompressionBackend,
    tree_ravel_clients,
)
from repro.compression.ops import QSGDQuantizer, RandK
from repro.kernels import ops, ref
from repro.kernels.diana_shift import diana_shift_update
from repro.kernels.qsgd import TILE, qsgd_quantize
from repro.kernels.randk import (
    randk_compress,
    randk_decompress,
    randk_decompress_into,
    randk_mask,
)

REF = CompressionBackend("reference")
PAL = CompressionBackend("pallas")


# ---------------------------------------------------------------------------
# qsgd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_tiles", [1, 3, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("levels", [4, 8, 16])
def test_qsgd_matches_ref(n_tiles, dtype, levels):
    key = jax.random.key(n_tiles * levels)
    x = (jax.random.normal(key, (n_tiles * TILE,)) * 3).astype(dtype)
    u = jax.random.uniform(jax.random.key(7), x.shape)
    got = qsgd_quantize(x, u, levels=levels)
    want = ref.qsgd_quantize_ref(x, u, levels=levels, tile=TILE)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
    )


def test_qsgd_unbiased():
    """E[Q(x)] = x conditional on tile scales (Assumption 1)."""
    key = jax.random.key(0)
    x = jax.random.normal(key, (TILE,))
    reps = 512
    us = jax.random.uniform(jax.random.key(1), (reps, TILE))
    outs = jax.vmap(lambda u: qsgd_quantize(x, u, levels=4))(us)
    err = jnp.mean(outs, axis=0) - x
    scale = float(jnp.max(jnp.abs(x)))
    # MC std of the mean ~ scale/(4*sqrt(reps)); allow 5 sigma
    assert float(jnp.max(jnp.abs(err))) < 5 * scale / (4 * np.sqrt(reps))


def test_qsgd_wrapper_padding():
    x = jax.random.normal(jax.random.key(2), (TILE + 13, 7))
    out = ops.qsgd(x, jax.random.key(3))
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# randk circular row-block gather/scatter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_blocks,k_blocks", [(5, 1), (5, 2), (8, 8), (16, 3)])
@pytest.mark.parametrize("d", [16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_randk_roundtrip_all_starts(n_blocks, k_blocks, d, dtype):
    br = 8
    rows = (jax.random.normal(jax.random.key(0), (n_blocks * br, d)) * 2).astype(dtype)
    for start in range(n_blocks):  # includes every wrap position
        s = jnp.int32(start)
        got_v = randk_compress(rows, s, k_blocks=k_blocks, block_rows=br)
        want_v = ref.randk_compress_ref(rows, s, k_blocks=k_blocks, block_rows=br)
        np.testing.assert_allclose(np.asarray(got_v, np.float32),
                                   np.asarray(want_v, np.float32), rtol=1e-2)
        got_d = randk_decompress(got_v, s, n_rows=n_blocks * br, block_rows=br)
        want_d = ref.randk_decompress_ref(want_v, s, n_rows=n_blocks * br,
                                          block_rows=br)
        np.testing.assert_allclose(np.asarray(got_d, np.float32),
                                   np.asarray(want_d, np.float32), rtol=1e-2)


@pytest.mark.parametrize("n_blocks,k_blocks,slots", [(5, 2, 1), (4, 4, 1),
                                                      (6, 3, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_randk_decompress_into_matches_ref(n_blocks, k_blocks, slots, dtype):
    """The in-place write-back touches exactly the window's blocks of the
    chosen slot segment, for every start (wrap-around included)."""
    br, d = 8, 16
    n = n_blocks * br
    into = jax.random.normal(jax.random.key(0), (slots * n, d)).astype(dtype)
    vals = jax.random.normal(jax.random.key(1), (k_blocks * br, d))
    base = jnp.int32((slots - 1) * n_blocks)
    for start in range(n_blocks):
        s = jnp.int32(start)
        got = randk_decompress_into(into, vals, s, base, n_rows=n,
                                    block_rows=br)
        want = ref.randk_decompress_into_ref(into, vals, s, base, n_rows=n,
                                             block_rows=br)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32)), start
        blocks = np.asarray(want, np.float32).reshape(-1, br, d)
        touched = {int(base) + (start + i) % n_blocks
                   for i in range(k_blocks)}
        kept = [b for b in range(slots * n_blocks) if b not in touched]
        assert np.array_equal(
            blocks[kept],
            np.asarray(into, np.float32).reshape(-1, br, d)[kept])


def test_randk_unbiased_over_starts():
    """Mean over all start blocks reconstructs the original rows exactly."""
    br, nb, d = 8, 6, 32
    rows = jax.random.normal(jax.random.key(1), (nb * br, d))
    acc = jnp.zeros_like(rows)
    for start in range(nb):
        v = randk_compress(rows, jnp.int32(start), k_blocks=2, block_rows=br)
        acc = acc + randk_decompress(v, jnp.int32(start), n_rows=nb * br,
                                     block_rows=br)
    np.testing.assert_allclose(np.asarray(acc / nb), np.asarray(rows), atol=1e-4)


# ---------------------------------------------------------------------------
# fused diana shift update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 128 * 600, 128 * 600 + 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_diana_shift_matches_ref(n, dtype):
    ks = jax.random.split(jax.random.key(4), 4)
    h, qo, mh, qm = (jax.random.normal(k, (n,)).astype(dtype) for k in ks)
    got = diana_shift_update(h, qo, mh, qm, alpha=0.11)
    want = ref.diana_shift_update_ref(h, qo, mh, qm, 0.11)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=5e-2 if dtype == jnp.bfloat16 else 1e-6)


def test_diana_shift_beta_second_stepsize():
    """The mean-shift update takes its own stepsize beta (fleets pass
    mean_scale*alpha, DESIGN.md §3.9): kernel matches reference for
    beta != alpha, and the beta=None default is bitwise the beta=alpha
    path — the no-rescale configs keep their exact trajectory."""
    n = 128 * 3
    ks = jax.random.split(jax.random.key(7), 4)
    h, qo, mh, qm = (jax.random.normal(k, (n,)) for k in ks)
    alpha, beta = 0.25, 0.0625  # beta = (M/C) * alpha at M/C = 1/4
    got = diana_shift_update(h, qo, mh, qm, alpha=alpha, beta=beta)
    want = ref.diana_shift_update_ref(h, qo, mh, qm, alpha, beta)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)
    # only the mean-shift output moves with beta
    base = diana_shift_update(h, qo, mh, qm, alpha=alpha)
    assert np.array_equal(np.asarray(got[1]), np.asarray(base[1]))
    assert not np.array_equal(np.asarray(got[2]), np.asarray(base[2]))
    np.testing.assert_allclose(np.asarray(got[2]),
                               np.asarray(mh) + beta * np.asarray(qm),
                               atol=1e-6)
    for defaulted, explicit in zip(
            base, diana_shift_update(h, qo, mh, qm, alpha=alpha, beta=alpha)):
        assert np.asarray(defaulted).tobytes() == \
            np.asarray(explicit).tobytes()


def test_backend_parity_diana_shift_beta():
    ks = jax.random.split(jax.random.key(27), 4)
    trees = [jax.tree.map(lambda l, kk=kk: jax.random.normal(kk, l.shape), TREE)
             for kk in ks]
    got = PAL.tree_diana_shift(*trees, alpha=0.17, beta=0.03)
    want = REF.tree_diana_shift(*trees, alpha=0.17, beta=0.03)
    for gt, wt in zip(got, want):
        for a, b in zip(jax.tree.leaves(gt), jax.tree.leaves(wt)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_diana_shift_fixed_point():
    """At the DIANA fixed point (h == g, q == 0) the direction is H_t and
    shifts do not move — the Theorem 2 stationarity on the kernel path."""
    n = 256
    h = jax.random.normal(jax.random.key(5), (n,))
    zeros = jnp.zeros_like(h)
    direction, h2, mh2 = ops.diana_shift(h, zeros, h, zeros, alpha=0.5)
    np.testing.assert_allclose(np.asarray(direction), np.asarray(h), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mh2), np.asarray(h), atol=1e-6)


# ---------------------------------------------------------------------------
# fused dense Rand-k mask (simulator hot path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,dp", [(1024, 1024), (2500, 3072), (130, 1024)])
@pytest.mark.parametrize("k", [1, 13, 100])
def test_randk_mask_matches_ref(d, dp, k):
    k = min(k, d)
    m = 3
    x = jax.random.normal(jax.random.key(0), (m, dp))
    x = x * (jnp.arange(dp) < d)  # padding region zero, as callers guarantee
    starts = jnp.array([0, d - 1, d // 2], jnp.int32)
    got = randk_mask(x, starts, d=d, k=k)
    want = ref.randk_mask_ref(x, starts, d=d, k=k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # exactly k real coordinates survive per client (a.s. for dense x)
    nnz = np.count_nonzero(np.asarray(got[:, :d]) != 0, axis=1)
    dense_rows = np.count_nonzero(np.asarray(x[:, :d]), axis=1) == d
    assert np.all(nnz[dense_rows] == k)


# ---------------------------------------------------------------------------
# backend-level parity: backend="reference" vs backend="pallas"
# ---------------------------------------------------------------------------

TREE = {
    "w": jax.random.normal(jax.random.key(11), (4, 37, 13)),
    "b": jax.random.normal(jax.random.key(12), (4, 129)),
}


@pytest.mark.parametrize("comp", [RandK(fraction=0.1), RandK(k=7),
                                  QSGDQuantizer(levels=8)],
                         ids=["randk_frac", "randk_k", "qsgd"])
def test_backend_parity_compress_clients(comp):
    key = jax.random.key(3)
    got = PAL.compress_clients(comp, key, TREE)
    want = REF.compress_clients(comp, key, TREE)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_backend_parity_diana_shift():
    ks = jax.random.split(jax.random.key(21), 4)
    trees = [jax.tree.map(lambda l, kk=kk: jax.random.normal(kk, l.shape), TREE)
             for kk in ks]
    got = PAL.tree_diana_shift(*trees, alpha=0.17)
    want = REF.tree_diana_shift(*trees, alpha=0.17)
    for gt, wt in zip(got, want):
        for a, b in zip(jax.tree.leaves(gt), jax.tree.leaves(wt)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_backend_parity_wire_roundtrip():
    rows = jax.random.normal(jax.random.key(31), (40, 16))
    for start in range(5):
        s = jnp.int32(start)
        vp = PAL.wire_compress(rows, s, k_blocks=2, block_rows=8)
        vr = REF.wire_compress(rows, s, k_blocks=2, block_rows=8)
        np.testing.assert_allclose(np.asarray(vp), np.asarray(vr), atol=1e-6)
        dp_ = PAL.wire_decompress(vp, s, n_rows=40, block_rows=8)
        dr = REF.wire_decompress(vr, s, n_rows=40, block_rows=8)
        np.testing.assert_allclose(np.asarray(dp_), np.asarray(dr), atol=1e-6)


def test_backend_unknown_name_raises():
    with pytest.raises(ValueError):
        CompressionBackend("cuda")


# ---------------------------------------------------------------------------
# statistical guarantees of the sort-free (circular-window) Rand-k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("be", [REF, PAL], ids=["reference", "pallas"])
def test_sortfree_randk_unbiased(be):
    """E[Q(x)] = x over window starts (Assumption 1 for the backend path)."""
    comp = RandK(fraction=0.2)
    mat, _ = tree_ravel_clients(TREE)
    reps = 3000
    keys = jax.random.split(jax.random.key(41), reps)
    outs = jax.vmap(
        lambda k: tree_ravel_clients(be.compress_clients(comp, k, TREE))[0]
    )(keys)
    mean = jnp.mean(outs, axis=0)
    se = jnp.std(outs, axis=0) / np.sqrt(reps)
    viol = jnp.abs(mean - mat) > 6 * se + 1e-4
    assert int(viol.sum()) == 0


def test_sortfree_randk_omega_exact():
    """E||Q(x)-x||^2 = (d/k - 1)||x||^2 exactly — the window sampler keeps
    the Rand-k variance constant (marginal inclusion probability k/d)."""
    comp = RandK(k=8)
    d = 64
    x = jax.random.normal(jax.random.key(51), (d,))
    keys = jax.random.split(jax.random.key(52), 20000)
    qs = jax.vmap(lambda k: comp.compress(k, x))(keys)
    var = float(jnp.mean(jnp.sum((qs - x[None]) ** 2, axis=-1)))
    expect = (d / 8 - 1) * float(jnp.sum(x**2))
    assert abs(var - expect) / expect < 0.05


def test_sortfree_randk_window_is_contiguous():
    """The selected support is a circular window — the property that makes
    the sampler sort-free and the kernel gather block-contiguous."""
    comp = RandK(k=5)
    x = jnp.ones((12,))
    for seed in range(61, 93):
        q = np.asarray(comp.compress(jax.random.key(seed), x))
        support = set(np.nonzero(q)[0].tolist())
        # the window may wrap past the end: its start is the one support
        # index whose circular predecessor is outside the support
        starts = [i for i in support if (i - 1) % 12 not in support]
        assert len(starts) == 1, (seed, sorted(support))
        assert support == {(starts[0] + j) % 12 for j in range(5)}, seed
