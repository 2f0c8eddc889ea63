"""Bit-packed wire slab kernels (kernels/pack.py vs the ref.py oracle).

The packed wire's contract (DESIGN.md §3.13) splits into two halves:

  transport   the packed BYTES are bitwise identical between the pallas
              kernels and the jnp reference — the lattice is integer math,
              so there is no tolerance to hide behind. Scales are one f32
              division and may differ by an ulp across compilation contexts
              (XLA reciprocal-multiply vs true divide), so they compare at
              the repo's standard oracle tolerance.
  decode      v = (b - L) * scale is the ONLY dequantization formula; both
              the f32-transport quantized wire and the packed wire
              round-trip through it, which is what makes packed8 transport
              bit-match the f32 wire at equal levels (test_pod_wire.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.pack import pack_slab, unpack_reduce, unpack_slab
from repro.kernels.randk import BLOCK_ROWS


def _slab(rows, d, seed, scale=3.0):
    key = jax.random.key(seed)
    vals = jax.random.normal(key, (rows, d), jnp.float32) * scale
    u = jax.random.uniform(jax.random.key(seed + 1), (rows, d))
    return vals, u


# ---------------------------------------------------------------------------
# pallas vs reference: bytes bitwise, scales at oracle tolerance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nibble", [False, True])
@pytest.mark.parametrize("rows,d", [(8, 128), (16, 64), (13, 5), (64, 32)])
def test_pack_matches_ref(rows, d, nibble):
    levels = 7 if nibble else 127
    vals, u = _slab(rows, d, seed=rows * d)
    p, s = pack_slab(vals, u, levels=levels, nibble=nibble)
    pr, sr = ref.pack_slab_ref(vals, u, levels=levels, nibble=nibble,
                               block_rows=BLOCK_ROWS)
    assert p.dtype == jnp.uint8 and pr.dtype == jnp.uint8
    assert np.array_equal(np.asarray(p), np.asarray(pr))  # bitwise
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-6, atol=0)


@pytest.mark.parametrize("nibble", [False, True])
@pytest.mark.parametrize("rows,d", [(8, 128), (13, 5), (24, 16)])
def test_unpack_matches_ref(rows, d, nibble):
    levels = 7 if nibble else 127
    vals, u = _slab(rows, d, seed=3 + rows)
    p, s = pack_slab(vals, u, levels=levels, nibble=nibble)
    got = unpack_slab(p, s, levels=levels, n_rows=rows, nibble=nibble)
    want = ref.unpack_slab_ref(p, s, levels=levels, n_rows=rows,
                               nibble=nibble)
    assert got.shape == (rows, d)
    # same bytes, same scales -> same decode, bitwise
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("nibble", [False, True])
@pytest.mark.parametrize("rows", [72, 200])
def test_row_blocked_grid_matches_ref(monkeypatch, rows, nibble):
    """With a small block budget every kernel walks several row blocks (and
    unpack_reduce a (row block, rank) grid), padding the last one: bytes,
    decode and rank-order mean still match the oracles bitwise."""
    from repro.kernels import pack

    monkeypatch.setattr(pack, "_BLOCK_BYTES", 32 * 4 * 128)
    jax.clear_caches()
    levels, d, ranks = (7 if nibble else 127), 128, 4
    assert pack._row_block(rows + (-rows) % BLOCK_ROWS, d, nibble) < rows
    slabs = [_slab(rows, d, seed=97 + r) for r in range(ranks)]
    packed, scales = zip(*(pack_slab(v, u, levels=levels, nibble=nibble)
                           for v, u in slabs))
    for (v, u), p, s in zip(slabs, packed, scales):
        pr, sr = ref.pack_slab_ref(v, u, levels=levels, nibble=nibble,
                                   block_rows=BLOCK_ROWS)
        assert np.array_equal(np.asarray(p), np.asarray(pr))
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-6, atol=0)
        assert np.array_equal(
            np.asarray(unpack_slab(p, s, levels=levels, n_rows=rows,
                                   nibble=nibble)),
            np.asarray(ref.unpack_slab_ref(p, s, levels=levels, n_rows=rows,
                                           nibble=nibble)))
    gp, gs = jnp.stack(packed), jnp.stack(scales)
    assert np.array_equal(
        np.asarray(unpack_reduce(gp, gs, levels=levels, n_rows=rows,
                                 nibble=nibble)),
        np.asarray(ref.unpack_reduce_ref(gp, gs, levels=levels, n_rows=rows,
                                         nibble=nibble)))
    jax.clear_caches()


# ---------------------------------------------------------------------------
# round-trip properties of the lattice
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nibble,levels", [(False, 127), (False, 3),
                                           (True, 7), (True, 2)])
def test_roundtrip_decode_is_exact_lattice(nibble, levels):
    """Decoding reproduces exactly (q - L) * scale for the integer lattice
    the quantizer chose — recomputed independently in numpy float64."""
    rows, d = 16, 32
    vals, u = _slab(rows, d, seed=11)
    p, s = pack_slab(vals, u, levels=levels, nibble=nibble)
    got = np.asarray(unpack_slab(p, s, levels=levels, n_rows=rows,
                                 nibble=nibble))
    # independent byte decode
    b = np.asarray(p).astype(np.int64)
    if nibble:
        prows = b.shape[0]
        b = np.stack([b % 16, b // 16], axis=1).reshape(prows * 2, d)
    assert (b >= 0).all() and (b <= 2 * levels).all()
    want = (b.astype(np.float32) - np.float32(levels)) * np.asarray(s)[:rows]
    assert np.array_equal(got, want[:rows])


@pytest.mark.parametrize("rows", [1, 5, 9, 13])
def test_padding_tail_decodes_to_zero(rows):
    """Rows pad to a BLOCK_ROWS multiple; padding quantizes to the zero
    byte (b = L), so a full-width decode puts exact zeros in the tail and
    the n_rows trim loses nothing."""
    d = 16
    vals, u = _slab(rows, d, seed=rows)
    p, s = pack_slab(vals, u, levels=127)
    kp = s.shape[0]
    assert kp == rows + (-rows) % BLOCK_ROWS
    full = np.asarray(unpack_slab(p, s, levels=127, n_rows=kp))
    assert (full[rows:] == 0).all()
    got = unpack_slab(p, s, levels=127, n_rows=rows)
    assert got.shape == (rows, d)
    assert np.array_equal(np.asarray(got), full[:rows])


def test_nibble_dequant_identity_at_shared_levels():
    """At L = 7 the nibble lane carries the same lattice as the full byte:
    pack(nibble=True) must decode bitwise-identically to pack(nibble=False)
    at the same levels — the packing is transport, not quantization."""
    rows, d = 16, 32
    vals, u = _slab(rows, d, seed=21)
    p8, s8 = pack_slab(vals, u, levels=7, nibble=False)
    p4, s4 = pack_slab(vals, u, levels=7, nibble=True)
    assert p4.shape == (p8.shape[0] // 2, d)  # two rows per byte
    assert np.array_equal(np.asarray(s8), np.asarray(s4))
    v8 = unpack_slab(p8, s8, levels=7, n_rows=rows, nibble=False)
    v4 = unpack_slab(p4, s4, levels=7, n_rows=rows, nibble=True)
    assert np.array_equal(np.asarray(v8), np.asarray(v4))


def test_quantizer_unbiased():
    """E[decode(pack(x))] = x over the rounding uniforms (Assumption 1 for
    the wire quantizer; omega is set by levels, not by the transport)."""
    rows, d, levels, reps = 8, 16, 7, 4000
    vals = jax.random.normal(jax.random.key(0), (rows, d), jnp.float32)

    def one(key):
        u = jax.random.uniform(key, (rows, d))
        p, s = pack_slab(vals, u, levels=levels)
        return unpack_slab(p, s, levels=levels, n_rows=rows)

    outs = jax.lax.map(one, jax.random.split(jax.random.key(1), reps))
    err = np.asarray(jnp.mean(outs, axis=0) - vals)
    # per-entry MC std <= scale_r/(2 sqrt(reps)); scale_r = amax_r / levels
    amax = np.abs(np.asarray(vals)).max(axis=1, keepdims=True)
    tol = 3.0 * amax / levels / (2 * np.sqrt(reps))
    assert (np.abs(err) < tol + 1e-6).all(), np.abs(err / amax).max()


# ---------------------------------------------------------------------------
# fused unpack-reduce (the receive half of the packed collective)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nibble", [False, True])
@pytest.mark.parametrize("ranks", [2, 4, 8])
def test_unpack_reduce_matches_ref(ranks, nibble):
    levels = 7 if nibble else 127
    rows, d = 16, 32
    packed, scales = [], []
    for r in range(ranks):
        vals, u = _slab(rows, d, seed=100 + r)
        p, s = pack_slab(vals, u, levels=levels, nibble=nibble)
        packed.append(p)
        scales.append(s)
    packed = jnp.stack(packed)
    scales = jnp.stack(scales)
    got = unpack_reduce(packed, scales, levels=levels, n_rows=rows,
                        nibble=nibble)
    want = ref.unpack_reduce_ref(packed, scales, levels=levels, n_rows=rows,
                                 nibble=nibble)
    assert got.shape == (rows, d)
    assert np.array_equal(np.asarray(got), np.asarray(want))  # same schedule


def test_unpack_reduce_is_mean_of_decodes():
    """The fused kernel equals the mean of individually decoded slabs on
    power-of-two rank counts (rank-order sum, exact /R division) — the
    property that lets the packed wire stand in for lax.pmean."""
    ranks, rows, d, levels = 4, 16, 32, 127
    packed, scales = [], []
    for r in range(ranks):
        vals, u = _slab(rows, d, seed=200 + r)
        p, s = pack_slab(vals, u, levels=levels)
        packed.append(p)
        scales.append(s)
    fused = unpack_reduce(jnp.stack(packed), jnp.stack(scales),
                          levels=levels, n_rows=rows)
    acc = unpack_slab(packed[0], scales[0], levels=levels, n_rows=rows)
    for r in range(1, ranks):
        acc = acc + unpack_slab(packed[r], scales[r], levels=levels,
                                n_rows=rows)
    assert np.array_equal(np.asarray(fused), np.asarray(acc / float(ranks)))


def test_unpack_reduce_weighted_scales_fold():
    """Elastic weights fold into the scale sideband: reducing with scales
    w_r * s_r equals the weighted mean of decodes for exact (0/1) weights —
    a dropped rank contributes exact zeros."""
    ranks, rows, d, levels = 4, 8, 16, 127
    weights = [1.0, 0.0, 1.0, 1.0]
    packed, scales = [], []
    for r in range(ranks):
        vals, u = _slab(rows, d, seed=300 + r)
        p, s = pack_slab(vals, u, levels=levels)
        packed.append(p)
        scales.append(s * weights[r])
    fused = np.asarray(unpack_reduce(jnp.stack(packed), jnp.stack(scales),
                                     levels=levels, n_rows=rows))
    acc = np.zeros((rows, d), np.float32)
    for r in (0, 2, 3):
        acc += np.asarray(unpack_slab(packed[r], scales[r], levels=levels,
                                      n_rows=rows))
    assert np.array_equal(fused, acc / np.float32(ranks))
