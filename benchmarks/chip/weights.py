"""Seeded weights, made the same way for the program and for the reference.

Each leaf is drawn from its own key, folded from the run's seed with a hash
of the leaf's path, so the draw of one leaf does not depend on the others:
norm scales are 1, biases 0.02 N(0, 1), the embedding and the LM head
0.02 N(0, 1), and every other matrix N(0, 1) / sqrt(fan_in), with fan_in
the second-to-last axis. Values are drawn in float32 and rounded once to
the leaf's dtype; the reference keeps those rounded values in float32.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: int = 0) -> jax.Array:
    """A threefry key from any non-negative integer seed, 64-bit ones too."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def rounded(x, dtype):
    """x (float32) rounded to the nearest value of `dtype`, kept in
    float32. `reduce_precision` is never folded away, as a convert to
    bfloat16 and back inside one program may be."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def draw_leaf(key, name: str, shape, dtype):
    """One leaf's initial values, as float32 values of `dtype`."""
    last = name.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if last == "scale":
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(k, shape, jnp.float32)
    if last in ("embed", "lm_head") or len(shape) < 2 or last.startswith("b"):
        return rounded(0.02 * z, dtype)
    return rounded(z / np.sqrt(shape[-2]), dtype)


def make_params(key, abstract, dtype=None):
    """Fill a tree of ShapeDtypeStructs, in each leaf's dtype or, given
    `dtype`, in that one; call under jit."""
    return jax.tree_util.tree_map_with_path(
        lambda p, a: draw_leaf(key, path_name(p), a.shape, a.dtype).astype(
            dtype or a.dtype), abstract)


@partial(jax.jit, static_argnums=2)
def _distance(key, params, kinds):
    out = []
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for (path, p), (shape, dtype) in zip(flat, kinds):
        p0 = draw_leaf(key, path_name(path), shape, jnp.dtype(dtype))
        out.append(jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32) - p0))))
    return jnp.stack(out)


def init_distance(key, params, abstract=None):
    """Per leaf, the norm of `params` minus the seeded initial weights.
    `abstract` gives the dtype the weights were drawn in, when `params`
    holds them in another."""
    abstract = params if abstract is None else abstract
    kinds = tuple((tuple(a.shape), jnp.dtype(a.dtype).name)
                  for a in jax.tree.leaves(abstract))
    return _distance(key, params, kinds)
