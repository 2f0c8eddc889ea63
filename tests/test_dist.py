"""Production wire (core.dist): shard_map aggregation semantics.

Runs on 8 forced host devices (mesh 4x2 = data x model), set in conftest for
this module only via a subprocess-free trick: these tests are skipped unless
the session was started with at least 8 devices — `tests/conftest.py` forces
8 host devices for the whole test session (smoke tests use a mesh-free path,
so this is safe; the 512-device production mesh is ONLY in launch/dryrun.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.dist import CompressedAggregation

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices"
)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _mesh():
    return jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


GRADS = {
    "w": jnp.arange(4 * 64, dtype=jnp.float32).reshape(4, 64) / 100.0,
    "b": jnp.ones((4, 8), jnp.float32),
}
SPECS = {"w": P("data", "model"), "b": P("data", None)}
MEAN = jax.tree.map(lambda x: x.mean(0), GRADS)


def _run_rounds(agg, rounds):
    def body(g):
        g = jax.tree.map(lambda x: x[0], g)
        state = agg.init(g)
        key = jax.random.PRNGKey(0)

        def one(state, t):
            d, state = agg.aggregate(g, state, jax.random.fold_in(key, t))
            return state, d

        _, ds = jax.lax.scan(one, state, jnp.arange(rounds))
        d = jax.tree.map(lambda x: x[-1], ds)
        return jax.tree.map(lambda x: x[None], d)

    out = jax.jit(
        _shard_map(body, _mesh(), (SPECS,), SPECS)
    )(GRADS)
    return jax.tree.map(lambda x: x[0], out)


def test_dense_is_exact_mean():
    agg = CompressedAggregation(method="dense", client_axes=("data",))
    got = _run_rounds(agg, 1)
    for k in GRADS:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(MEAN[k]), rtol=1e-6)


def test_diana_shared_converges_to_exact_mean():
    """Fixed gradients: shifts absorb them; direction -> exact mean (Thm 2
    fixed-point logic on the production wire)."""
    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.25,
                                client_axes=("data",), shift_dtype=jnp.float32)
    got = _run_rounds(agg, 200)
    for k in GRADS:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(MEAN[k]), atol=1e-5)


def test_diana_independent_converges():
    agg = CompressedAggregation(method="diana", wire="independent", fraction=0.5,
                                client_axes=("data",), shift_dtype=jnp.float32)
    got = _run_rounds(agg, 300)
    for k in GRADS:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(MEAN[k]), atol=5e-2)


def test_q_shared_unbiased():
    """Averaging many Q-rounds approaches the true mean (unbiasedness)."""
    agg = CompressedAggregation(method="q", wire="shared", fraction=0.25,
                                client_axes=("data",))

    def body(g):
        g = jax.tree.map(lambda x: x[0], g)
        key = jax.random.PRNGKey(0)

        def one(acc, t):
            d, _ = agg.aggregate(g, None, jax.random.fold_in(key, t))
            return jax.tree.map(jnp.add, acc, d), None

        acc, _ = jax.lax.scan(one, jax.tree.map(jnp.zeros_like, g), jnp.arange(2000))
        acc = jax.tree.map(lambda a: a / 2000.0, acc)
        return jax.tree.map(lambda x: x[None], acc)

    out = jax.jit(
        _shard_map(body, _mesh(), (SPECS,), SPECS)
    )(GRADS)
    got = jax.tree.map(lambda x: x[0], out)
    for k in GRADS:
        scale = float(jnp.abs(MEAN[k]).max())
        assert float(jnp.abs(got[k] - MEAN[k]).max()) < 0.15 * scale + 0.05


def test_shift_lr_default_matches_theory():
    agg = CompressedAggregation(fraction=0.02)
    assert abs(agg.shift_lr - 0.02) < 1e-9  # 1/(1+omega) = k/d
    agg2 = CompressedAggregation(fraction=0.25, alpha=0.1)
    assert agg2.shift_lr == 0.1
