"""Two-level (hierarchical) pod wire: parity, statistics, fixed point, and
the NASTYA mapping's equivalence with the simulator (core/algorithms.py).

All tests run the wire the way production does — inside a fully-manual
shard_map over every mesh axis (core/dist.py docstring) — on the forced
8-host-device session (conftest). Meshes come from the conftest fixtures:

  mesh_4x2    flat wire          (4 clients x 2 TP)
  mesh_1x4x2  two-level, 1 pod   (must bit-match mesh_4x2)
  mesh_2x2x2  two-level, 2 pods  (both levels live)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.dist import CompressedAggregation, DianaState
from repro.data.logreg import make_federated_logreg

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices"
)


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(mesh.axis_names), check_vma=False)


GRADS = {
    "w": jnp.arange(4 * 64, dtype=jnp.float32).reshape(4, 64) / 100.0,
    "b": jnp.ones((4, 8), jnp.float32),
}
MEAN = jax.tree.map(lambda x: x.mean(0), GRADS)


def _wire_specs(mesh, grads):
    """Stacked-client specs for `grads` on `mesh`: leading dim = all client
    ranks, trailing dim TP when it divides."""
    caxes = tuple(n for n in mesh.axis_names if n != "model")
    msize = int(mesh.shape["model"])
    return jax.tree.map(
        lambda x: P(caxes, *(None,) * (x.ndim - 2),
                    "model" if x.shape[-1] % msize == 0 else None), grads)


def _configure(agg, mesh):
    from repro.launch.steps import configure_agg

    return configure_agg(agg, mesh)


def _run_rounds(agg, mesh, rounds, *, grads=GRADS, seed=0, slots=None,
                reduce="last"):
    """Direction of `rounds` aggregate() calls (per-client fixed gradients),
    executed inside the fully-manual wire region. `slots` is an optional
    (rounds,) vector of shared slot ids for per-slot methods; `reduce` picks
    the last round's direction or the running mean over rounds."""
    agg = _configure(agg, mesh)
    specs = _wire_specs(mesh, grads)
    slot_seq = (jnp.zeros((rounds,), jnp.int32) if slots is None
                else jnp.asarray(slots, jnp.int32))

    def body(g):
        g = jax.tree.map(lambda x: x[0], g)
        state = agg.init(g)
        key = jax.random.PRNGKey(seed)

        def one(state, inp):
            t, slot = inp
            d, state = agg.aggregate(g, state, jax.random.fold_in(key, t),
                                     slot=slot)
            return state, d

        _, ds = jax.lax.scan(one, state, (jnp.arange(rounds), slot_seq))
        if reduce == "mean":
            d = jax.tree.map(lambda x: jnp.mean(x, axis=0), ds)
        else:
            d = jax.tree.map(lambda x: x[-1], ds)
        return jax.tree.map(lambda x: x[None], d)

    out = jax.jit(_shard_map(body, mesh, (specs,), specs))(grads)
    return jax.tree.map(lambda x: x[0], out)


# ---------------------------------------------------------------------------
# parity: 1-pod two-level == flat single-level, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["q", "diana", "diana_rr", "ef"])
def test_one_pod_two_level_bit_matches_flat(method, mesh_4x2, mesh_1x4x2):
    """A single pod has no inter-pod link: the outer exchange is the exact
    identity, and the inner exchange draws the very same keys as the flat
    wire — the acceptance-criteria bit-match. Holds for every shift rule,
    per-slot tables and error-feedback residuals included."""
    agg = CompressedAggregation(method=method, wire="shared", fraction=0.25,
                                n_slots=3 if method == "diana_rr" else 1,
                                shift_dtype=jnp.float32)
    slots = np.arange(7) % 3 if method == "diana_rr" else None
    flat = _run_rounds(agg, mesh_4x2, 7, slots=slots)
    two = _run_rounds(agg, mesh_1x4x2, 7, slots=slots)
    for k in GRADS:
        assert np.array_equal(np.asarray(flat[k]), np.asarray(two[k])), k


def test_two_pod_wire_differs_from_flat(mesh_4x2, mesh_2x2x2):
    """Sanity for the parity test: with 2 real pods the outer level draws
    its own (salted) coordinates, so the wires must NOT coincide."""
    agg = CompressedAggregation(method="q", wire="shared", fraction=0.25)
    flat = _run_rounds(agg, mesh_4x2, 1)
    two = _run_rounds(agg, mesh_2x2x2, 1)
    assert any(
        not np.array_equal(np.asarray(flat[k]), np.asarray(two[k]))
        for k in GRADS
    )


# ---------------------------------------------------------------------------
# packed transports: bit-match the f32 wire at equal levels (DESIGN.md §3.13)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["q", "diana", "diana_rr"])
def test_packed8_bit_matches_f32_wire(method, mesh_4x2):
    """The tentpole guarantee: wire_dtype is transport, not math. Both modes
    round-trip the slab through the same pack->unpack kernels (same byte,
    same scale, same multiply), so moving the int8 lattice instead of the
    dequantized f32 slab changes NOTHING in the trajectory — params and
    shift state bitwise identical for every lossless shift rule."""
    n_slots = 3 if method == "diana_rr" else 1
    slots = np.arange(5) % 3 if method == "diana_rr" else None
    base = CompressedAggregation(method=method, wire="shared", fraction=0.25,
                                 n_slots=n_slots, shift_dtype=jnp.float32,
                                 wire_dtype="f32", wire_levels=127)
    packed = dataclasses.replace(base, wire_dtype="packed8", wire_levels=None)
    want = _run_rounds(base, mesh_4x2, 5, slots=slots)
    got = _run_rounds(packed, mesh_4x2, 5, slots=slots)
    for k in GRADS:
        assert np.array_equal(np.asarray(want[k]), np.asarray(got[k])), k


def test_packed8_bit_matches_f32_wire_two_pod(mesh_2x2x2):
    """Same guarantee with both wire levels live (2 pods): the inter-pod
    exchange packs and reduces with its own slab geometry and must stay
    bitwise exact too."""
    base = CompressedAggregation(method="diana_rr", wire="shared",
                                 fraction=0.25, n_slots=2,
                                 shift_dtype=jnp.float32,
                                 wire_dtype="f32", wire_levels=127)
    packed = dataclasses.replace(base, wire_dtype="packed8", wire_levels=None)
    slots = np.arange(4) % 2
    want = _run_rounds(base, mesh_2x2x2, 4, slots=slots)
    got = _run_rounds(packed, mesh_2x2x2, 4, slots=slots)
    for k in GRADS:
        assert np.array_equal(np.asarray(want[k]), np.asarray(got[k])), k


def test_packed4_bit_matches_f32_wire(mesh_4x2):
    """The nibble lane at its lossless cap L=7: two rows per byte on the
    wire, still bitwise identical to f32 transport at the same levels."""
    base = CompressedAggregation(method="diana", wire="shared", fraction=0.25,
                                 shift_dtype=jnp.float32,
                                 wire_dtype="f32", wire_levels=7)
    packed = dataclasses.replace(base, wire_dtype="packed4", wire_levels=None)
    want = _run_rounds(base, mesh_4x2, 3)
    got = _run_rounds(packed, mesh_4x2, 3)
    for k in GRADS:
        assert np.array_equal(np.asarray(want[k]), np.asarray(got[k])), k


def test_bf16_wire_close_to_f32(mesh_4x2):
    """bf16 transport is lossy (8 mantissa bits): no bit-match claim, but
    one round's direction must sit within downcast tolerance of the f32
    wire — the rounding enters only at the slab edges, not compounded."""
    base = CompressedAggregation(method="diana", wire="shared", fraction=0.25,
                                 shift_dtype=jnp.float32)
    bf = dataclasses.replace(base, wire_dtype="bf16")
    want = _run_rounds(base, mesh_4x2, 1)
    got = _run_rounds(bf, mesh_4x2, 1)
    rel = {}
    for k in GRADS:
        w = np.asarray(want[k])
        scale = np.abs(w).max() + 1e-12
        rel[k] = np.abs(np.asarray(got[k]) - w).max() / scale
        assert rel[k] < 1e-2, (k, rel[k])
    # the downcast is real: somewhere it must have rounded ("b" is all-ones,
    # exactly representable in bf16, so only "w" is guaranteed to move)
    assert max(rel.values()) > 0, rel


def test_packed_wire_byte_accounting(mesh_4x2):
    """True bytes on the wire: packed8 moves exactly slab/4 plus the 4B
    per-row f32 scale sideband (packed4 slab/8 + the same sideband) — the
    analytic identity the jaxpr census pins against the lowered step. On a
    matrix leaf the sideband is the +1/D term, keeping the total under
    0.26x / 0.135x of the f32 slab; 1-D cols=1 leaves pay the sideband per
    element and are a net LOSS (DESIGN.md §3.13)."""
    from repro.compression.backend import BLOCK_ROWS as BR
    from repro.core.dist import scale_sideband_bytes

    local = {"w": jnp.zeros((64, 128), jnp.float32)}
    aggs = {
        wd: _configure(
            CompressedAggregation(method="diana", wire="shared",
                                  fraction=0.25, shift_dtype=jnp.float32,
                                  wire_dtype=wd), mesh_4x2)
        for wd in ("f32", "bf16", "packed8", "packed4")
    }
    bytes_ = {wd: agg.wire_bytes_per_round(local)["intra_pod"]
              for wd, agg in aggs.items()}
    nb = 64 // BR
    slab_rows = max(1, int(0.25 * nb)) * BR
    sideband = scale_sideband_bytes("packed8", slab_rows)
    assert sideband == 4 * slab_rows
    assert bytes_["f32"] == slab_rows * 128 * 4
    assert bytes_["bf16"] == bytes_["f32"] // 2
    assert bytes_["packed8"] == bytes_["f32"] // 4 + sideband
    assert bytes_["packed4"] == bytes_["f32"] // 8 + sideband
    assert bytes_["packed8"] / bytes_["f32"] <= 0.26
    assert bytes_["packed4"] / bytes_["f32"] <= 0.135

    # the cols=1 caveat: a 1-D leaf's packed "compression" is a net loss
    flat = {"w": jnp.zeros((8192,), jnp.float32)}
    f32_flat = aggs["f32"].wire_bytes_per_round(flat)["intra_pod"]
    p8_flat = aggs["packed8"].wire_bytes_per_round(flat)["intra_pod"]
    assert p8_flat > f32_flat


# ---------------------------------------------------------------------------
# window path: the shared-wire DIANA update reads and writes only the
# Rand-block window (core/dist.py `_level_window`) — parity with a dense
# oracle built from the reference functions
# ---------------------------------------------------------------------------

# per-client leaves: rows not a multiple of 8 (30, and 3*12 = 36), a 1-D
# leaf, a bf16 gradient; "w" (30 rows, 4 blocks) is the one whose window
# start each case pins
WIN_SHAPES = {"b": ((20,), jnp.float32), "v": ((3, 12, 16), jnp.bfloat16),
              "w": ((30, 16), jnp.float32)}
WIN_LEAF = 2  # "w" in flatten order
WIN_CASES = [
    # method, mesh, elastic, transport, window start of "w", shift dtype
    ("diana", "flat", False, "f32", "first", "f32"),
    ("diana", "flat", False, "f32", "mid", "f32"),
    ("diana", "flat", False, "f32", "wrap", "f32"),
    ("diana", "flat", False, "f32", "full", "f32"),
    ("diana", "flat", True, "f32", "wrap", "bf16"),
    ("diana", "flat", False, "f32+levels", "mid", "f32"),
    ("diana", "flat", True, "bf16", "wrap", "f32"),
    ("diana", "flat", False, "packed8", "first", "bf16"),
    ("diana", "pods", False, "f32", "wrap", "f32"),
    ("diana", "pods", True, "packed8", "mid", "f32"),
    ("diana", "pods", False, "bf16", "full", "bf16"),
    ("diana", "pods", True, "f32+levels", "first", "bf16"),
    ("diana_rr", "flat", False, "f32", "mid", "f32"),
    ("diana_rr", "flat", True, "f32+levels", "wrap", "bf16"),
    ("diana_rr", "flat", False, "packed8", "full", "f32"),
    ("diana_rr", "pods", False, "f32", "first", "bf16"),
    ("diana_rr", "pods", True, "bf16", "wrap", "f32"),
    ("diana_rr", "pods", False, "f32+levels", "full", "f32"),
]


def _ulps(got, want, dtype, old):
    """|got - want| in units in the last place at `dtype` of the largest
    term of want = old + step: |want|, |old| or |step| (elementwise)."""
    info = jnp.finfo(dtype)
    got, want, old = (np.asarray(x, np.float32) for x in (got, want, old))
    big = np.maximum(np.maximum(np.abs(want), np.abs(old)),
                     np.abs(want - old))
    exp = np.frexp(big)[1] - 1
    ulp = np.maximum(np.ldexp(1.0, exp - info.nmant),
                     float(info.smallest_subnormal))
    return np.abs(got - want) / ulp


def _oracle_level(agg, grads, h_tree, mh_tree, key, *, axes, fraction,
                  alpha, beta, slot, weight):
    """One exchange level the dense way: payload over the whole leaf, the
    slab through the backend's (unchanged) transport, dense Q(x) from
    `randk_decompress_ref`, the update by `diana_shift_update_ref`."""
    from repro.compression.backend import BLOCK_ROWS as BR
    from repro.compression.backend import get_backend
    from repro.core.salts import WIRE_QUANT_SALT
    from repro.kernels import ref

    be = get_backend(agg.backend)
    dirs, hs, mhs = [], [], []
    leaves, treedef = jax.tree.flatten(grads)
    for i, (g, ht, mht) in enumerate(zip(leaves, jax.tree.leaves(h_tree),
                                         jax.tree.leaves(mh_tree))):
        h = ht if slot is None else ht[slot]
        mh = mht if slot is None else mht[slot]
        cols = g.shape[-1] if g.ndim >= 2 else 1
        p = (g.astype(jnp.float32) - h.astype(jnp.float32)).reshape(-1, cols)
        n = p.shape[0]
        p = jnp.pad(p, ((0, (-n) % BR), (0, 0)))
        nb = p.shape[0] // BR
        kb = max(1, int(fraction * nb))
        lkey = jax.random.fold_in(key, i)
        start = jax.random.randint(lkey, (), 0, nb)
        levels = agg._quant_levels
        quant_u = None if levels is None else jax.random.uniform(
            jax.random.fold_in(lkey, WIRE_QUANT_SALT), (kb * BR, cols))
        own, mean = be.wire_exchange(p, start, k_blocks=kb, block_rows=BR,
                                     axes=axes, weight=weight,
                                     wire_dtype=agg.wire_dtype,
                                     levels=levels, quant_u=quant_u)
        dense = lambda v: ref.randk_decompress_ref(
            v, start, n_rows=nb * BR, block_rows=BR)[:n].reshape(g.shape)
        d, h_new, mh_new = ref.diana_shift_update_ref(
            h, dense(own), mh, dense(mean), alpha, beta)
        dirs.append(d.astype(g.dtype))
        hs.append(h_new if slot is None else ht.at[slot].set(h_new))
        mhs.append(mh_new if slot is None else mht.at[slot].set(mh_new))
    return tuple(jax.tree.unflatten(treedef, x) for x in (dirs, hs, mhs))


def _window_rows(key, leaf: int, n: int, fraction: float) -> np.ndarray:
    """Rows of an n-row leaf inside the level's window (host side)."""
    nb = -(-n // 8)
    kb = max(1, int(fraction * nb))
    start = int(jax.random.randint(jax.random.fold_in(key, leaf), (), 0,
                                   nb))
    return np.asarray(sorted({(start * 8 + j) % (nb * 8)
                              for j in range(kb * 8)} & set(range(n))))


@pytest.mark.parametrize("method,mesh_name,elastic,transport,where,sdt",
                         WIN_CASES)
def test_window_path_matches_dense_oracle(method, mesh_name, elastic,
                                          transport, where, sdt, mesh_4x2,
                                          mesh_2x2x2):
    """diana/diana_rr on the shared wire update only the window: inside it
    h, H and the direction match the dense oracle within 1 ulp (bitwise
    where XLA contracts nothing); outside it h and H are bitwise their old
    values and the direction is the mean table cast to the gradient dtype.
    Flat and two-level meshes, elastic weights, every transport, window
    starts at block 0, mid, the wrap-around last block and k/d = 1, rows
    not a multiple of 8, a 1-D leaf, f32 and bf16 shift tables."""
    from repro.core.salts import POD_KEY_SALT

    mesh = mesh_4x2 if mesh_name == "flat" else mesh_2x2x2
    shift_dtype = jnp.float32 if sdt == "f32" else jnp.bfloat16
    n_slots = 3 if method == "diana_rr" else 1
    slot = 1 if method == "diana_rr" else None
    fraction = 1.0 if where == "full" else 0.5
    agg = _configure(CompressedAggregation(
        method=method, wire="shared", fraction=fraction, n_slots=n_slots,
        shift_dtype=shift_dtype,
        wire_dtype="f32" if transport == "f32+levels" else transport,
        wire_levels=127 if transport == "f32+levels" else None), mesh)
    assert agg._window_path
    target = {"first": 0, "mid": 2, "wrap": 3, "full": 0}[where]
    seed = next(s for s in range(1000) if int(jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(s), WIN_LEAF), (), 0, 4))
        == target)
    key = jax.random.PRNGKey(seed)

    caxes = tuple(n for n in mesh.axis_names if n != "model")
    m = 4
    lead = (n_slots,) if slot is not None else ()
    ks = iter(jax.random.split(jax.random.PRNGKey(100 + seed), 16))
    grads = {k: jax.random.normal(next(ks), (m,) + s).astype(d)
             for k, (s, d) in WIN_SHAPES.items()}
    tables = [{k: (0.3 * jax.random.normal(next(ks), (m,) + lead + s)
                   ).astype(shift_dtype) for k, (s, _) in WIN_SHAPES.items()}
              for _ in range(4)]
    weights = jnp.asarray([0.5, 1.0, 0.0, 2.0], jnp.float32)

    def spec(x, extra=0):
        tp = "model" if x.ndim - 1 - extra >= 2 else None
        return P(caxes, *(None,) * (x.ndim - 2), tp)

    gspec = jax.tree.map(spec, grads)
    tspec = jax.tree.map(lambda x: spec(x, len(lead)), tables[0])
    pods = bool(agg.pod_axes)

    def body(g, h, mh, ph, pmh, w):
        one = lambda t: jax.tree.map(lambda x: x[0], t)
        g, h, mh, ph, pmh = map(one, (g, h, mh, ph, pmh))
        wt = w[0] if elastic else None
        state = DianaState(h, mh, ph if pods else None,
                           pmh if pods else None)
        got_d, st = agg.aggregate(g, state, key, slot=slot, weight=wt)
        got = (got_d, st.shifts, st.mean_shift,
               st.pod_shifts if pods else ph,
               st.pod_mean_shift if pods else pmh)
        d, h2, mh2 = _oracle_level(
            agg, g, h, mh, key, axes=agg.client_axes, fraction=fraction,
            alpha=agg.shift_lr, beta=agg._beta(agg.shift_lr), slot=slot,
            weight=wt)
        ph2, pmh2 = ph, pmh
        if pods:
            d, ph2, pmh2 = _oracle_level(
                agg, d, ph, pmh, jax.random.fold_in(key, POD_KEY_SALT),
                axes=agg.pod_axes, fraction=agg._pod_fraction,
                alpha=agg.pod_shift_lr, beta=None, slot=slot, weight=None)
        want = (d, h2, mh2, ph2, pmh2)
        return jax.tree.map(lambda x: x[None], (got, want))

    specs = (gspec, tspec, tspec, tspec, tspec)
    f = jax.jit(_shard_map(body, mesh, specs + (P(caxes),),
                           (specs, specs)))
    got, want = f(grads, *tables, weights)

    sel = (lambda x: x) if slot is None else (lambda x: x[:, slot])
    levels = [(key, 1, 2, tables[0], tables[1])]  # (key, h, H, old h, old H)
    if pods:
        levels.append((jax.random.fold_in(key, POD_KEY_SALT), 3, 4,
                       tables[2], tables[3]))
    for name, (shape, gdt) in WIN_SHAPES.items():
        i = sorted(WIN_SHAPES).index(name)
        n = int(np.prod(shape[:-1])) if len(shape) >= 2 else shape[0]
        # each output is old + step: its ulp is taken at the largest term,
        # so a step that cancels the old value is not held to the ulp of
        # the small remainder (the direction's old value is the last
        # level's mean table)
        before = [tables[3 if pods else 1][name]] + [t[name]
                                                     for t in tables]
        before[0] = sel(before[0])
        for j in range(5):
            dt = gdt if j == 0 else shift_dtype
            err = _ulps(got[j][name], want[j][name], dt, before[j])
            assert err.max() <= 1.0, (name, j, err.max())
        for lkey, hi, mhi, old_h, old_mh in levels:
            rows = _window_rows(lkey, i, n, fraction)
            outside = np.setdiff1d(np.arange(n), rows)
            as_rows = lambda x: np.asarray(sel(x), np.float32).reshape(
                m, n, -1)
            for j, old in ((hi, old_h), (mhi, old_mh)):
                assert np.array_equal(as_rows(got[j][name])[:, outside],
                                      as_rows(old[name])[:, outside]), (
                    name, j)
        last_mh = np.asarray(
            sel(tables[3 if pods else 1][name]).astype(gdt), np.float32)
        assert np.array_equal(
            np.asarray(got[0][name], np.float32).reshape(m, n, -1)[
                :, outside],
            last_mh.reshape(m, n, -1)[:, outside]), name
        if where == "wrap" and name == "w":
            assert set(_window_rows(key, i, n, fraction)) == set(
                range(24, 30)) | set(range(8))


# ---------------------------------------------------------------------------
# statistics: unbiased, composed variance bound (1+w1)(1+w2)
# ---------------------------------------------------------------------------

def test_two_level_q_unbiased_with_composed_variance(mesh_2x2x2):
    """E[Q2(Q1(x))] = x and E||Q2(Q1(x))||^2 <= (1+w1)(1+w2)||x||^2 (tower
    rule over the two independent draws). Every client holds the same x so
    the intra-pod mean is exactly Q1(x) and the bound is tight to sampling
    error. ~1e4 seeded trials, like tests/test_kernels.py."""
    trials = 10_000
    agg = _configure(
        CompressedAggregation(method="q", wire="shared", fraction=0.25),
        mesh_2x2x2)
    x = {"w": jnp.asarray(
        np.random.default_rng(7).normal(size=(4, 64)), jnp.float32)}
    x = {"w": jnp.broadcast_to(x["w"][:1], (4, 64))}  # same x on every client
    specs = {"w": P(("pod", "data"), "model")}

    def body(g):
        g = jax.tree.map(lambda x: x[0], g)
        key = jax.random.PRNGKey(3)

        def one(acc, t):
            d, _ = agg.aggregate(g, None, jax.random.fold_in(key, t))
            s, s2 = acc
            return (jax.tree.map(jnp.add, s, d),
                    s2 + sum(jnp.sum(jnp.square(l))
                             for l in jax.tree.leaves(d))), None

        zeros = jax.tree.map(jnp.zeros_like, g)
        (s, s2), _ = jax.lax.scan(one, (zeros, jnp.zeros(())),
                                  jnp.arange(trials))
        return jax.tree.map(lambda a: a[None] / trials, s), s2[None] / trials

    mean_d, second_moment = jax.jit(
        _shard_map(body, mesh_2x2x2, (specs,),
                   (specs, P(("pod", "data"))))
    )(x)
    got = np.asarray(mean_d["w"][0])
    want = np.asarray(x["w"][0])
    # unbiased: montecarlo error ~ sqrt(omega_composed/trials) * |x|
    scale = float(np.abs(want).max())
    assert float(np.abs(got - want).max()) < 0.3 * scale + 0.02

    omega1, omega2 = agg.omega(), agg.pod_omega()
    bound = (1 + omega1) * (1 + omega2) * float(np.sum(want**2))
    m2 = float(second_moment[0])
    # the composed second moment sits near the bound (shared draws make it
    # exact for identical clients) but must not exceed it beyond MC error
    assert m2 < bound * 1.05, (m2, bound)
    assert m2 > float(np.sum(want**2)) * (1 + omega2) * 0.95  # both levels real


# ---------------------------------------------------------------------------
# DIANA fixed point: pod-level shifts kill the inter-pod residual
# ---------------------------------------------------------------------------

def test_pod_shifts_drive_interpod_residual_to_zero(mesh_2x2x2):
    """Fixed heterogeneous gradients from the paper's logreg problem: with
    DIANA shifts at both levels the compressed residuals vanish and the
    two-level direction converges to the exact global mean (Theorem 2 logic,
    once per level)."""
    prob = make_federated_logreg(m=4, n_batches=2, batch=4, d=64, cond=50.0,
                                 seed=1)
    loss = prob.loss_fn()
    w0 = {"w": jnp.zeros((prob.d,), jnp.float32)}
    # per-client full-batch gradient at w0 — maximally heterogeneous
    grads = jax.vmap(
        lambda a, y: jax.grad(loss)(w0, {"a": a.reshape(-1, prob.d),
                                         "y": y.reshape(-1)})
    )(prob.data["a"], prob.data["y"])["w"]  # (4, d)
    grads = {"w": grads}
    mean = np.asarray(grads["w"]).mean(0)

    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.25,
                                shift_dtype=jnp.float32)
    got = _run_rounds(agg, mesh_2x2x2,
                      300, grads=grads)
    np.testing.assert_allclose(np.asarray(got["w"]), mean, atol=1e-5)


def test_one_level_alone_leaves_interpod_noise(mesh_2x2x2):
    """Control for the fixed-point test: method 'q' (no shifts anywhere)
    does NOT converge to the mean on the same problem — the shifts are what
    kill the residual, not the averaging."""
    prob = make_federated_logreg(m=4, n_batches=2, batch=4, d=64, cond=50.0,
                                 seed=1)
    loss = prob.loss_fn()
    w0 = {"w": jnp.zeros((prob.d,), jnp.float32)}
    grads = {"w": jax.vmap(
        lambda a, y: jax.grad(loss)(w0, {"a": a.reshape(-1, prob.d),
                                         "y": y.reshape(-1)})
    )(prob.data["a"], prob.data["y"])["w"]}
    mean = np.asarray(grads["w"]).mean(0)
    agg = CompressedAggregation(method="q", wire="shared", fraction=0.25)
    got = _run_rounds(agg, mesh_2x2x2, 300, grads=grads)
    assert float(np.abs(np.asarray(got["w"]) - mean).max()) > 1e-3


# ---------------------------------------------------------------------------
# per-slot (diana_rr) and error-feedback (ef) rules on the production wire
# ---------------------------------------------------------------------------

def _logreg_grads():
    prob = make_federated_logreg(m=4, n_batches=2, batch=4, d=64, cond=50.0,
                                 seed=1)
    loss = prob.loss_fn()
    w0 = {"w": jnp.zeros((prob.d,), jnp.float32)}
    grads = {"w": jax.vmap(
        lambda a, y: jax.grad(loss)(w0, {"a": a.reshape(-1, prob.d),
                                         "y": y.reshape(-1)})
    )(prob.data["a"], prob.data["y"])["w"]}
    return grads, np.asarray(grads["w"]).mean(0)


def test_per_slot_shifts_reach_fixed_point(mesh_2x2x2):
    """diana_rr on the two-level wire: every slot's control variates kill
    their compressed residual, so the direction converges to the exact mean
    no matter which slot a round lands on (Theorem 2 logic per slot)."""
    grads, mean = _logreg_grads()
    n_slots = 3
    agg = CompressedAggregation(method="diana_rr", wire="shared",
                                fraction=0.25, n_slots=n_slots,
                                shift_dtype=jnp.float32)
    got = _run_rounds(agg, mesh_2x2x2, 450, grads=grads,
                      slots=np.arange(450) % n_slots)
    np.testing.assert_allclose(np.asarray(got["w"]), mean, atol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ef_wire_fixed_point_on_logreg(mesh_4x2, seed):
    """Error feedback on the wire: the residual memory telescopes, so the
    RUNNING MEAN of the directions converges to the exact gradient mean at
    rate ||e_T||/T — while the memory-free 'q' wire's mean keeps the
    compression noise floor. (The EF remedy the paper cites, now production.)
    ||e_T|| is bounded but varies with the key stream, so T is chosen for
    the bound to hold on every seed, not for one stream's lucky residual.
    """
    grads, mean = _logreg_grads()
    agg = CompressedAggregation(method="ef", wire="shared", fraction=0.25,
                                shift_dtype=jnp.float32)
    got = _run_rounds(agg, mesh_4x2, 1200, grads=grads, reduce="mean",
                      seed=seed)
    scale = float(np.abs(mean).max())
    err_ef = float(np.abs(np.asarray(got["w"]) - mean).max())
    assert err_ef < 0.02 * scale + 1e-4, (err_ef, scale)


def test_per_slot_wire_matches_simulator_and_pipeline_order(mesh_4x2):
    """The acceptance cross-check: the flat-mesh `diana_rr` pod wire and the
    simulator's `make_epoch_fn("diana_rr")` walk the SAME trajectory at
    fraction=1.0 (exact compression), fed by the same `rr_shared` sampler —
    params AND the full per-slot shift tables agree, which also pins the
    wire's slot selection to the pipeline's epoch order."""
    from repro.core.algorithms import ALGORITHMS, init_algorithm, make_epoch_fn
    from repro.compression.ops import RandK
    from repro.data.pipeline import make_batch_stream, run_epochs, \
        shared_slots_for_step
    from repro.data.reshuffle import ReshuffleSampler
    from repro.launch import steps
    from repro.launch.mesh import num_clients
    from repro.models import transformer

    cfg = _tiny_cfg()
    mesh = mesh_4x2
    m = num_clients(mesh)
    n, seq = 3, 8
    gamma, alpha = 0.02, 0.5
    epochs = 2

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, size=(m, n, 1, seq + 1))  # (M,n,b,S+1)
    sim_data = {"tokens": jnp.asarray(tokens, jnp.int32)}
    sampler = ReshuffleSampler(m, n, mode="rr_shared", seed=5)

    loss_fn = lambda p, b: transformer.loss_fn(p, b, cfg, remat=False,
                                               seq_shard=False)
    params0 = transformer.init_params(jax.random.key(0), cfg)

    # --- simulator: run_epochs feeds the sampler's shared order -----------
    spec, epoch = make_epoch_fn("diana_rr", loss_fn, RandK(fraction=1.0),
                                gamma=gamma, alpha=alpha)
    sim = init_algorithm(ALGORITHMS["diana_rr"], params0, m, n)
    sim = run_epochs(epoch, sim, sim_data, sampler, epochs=epochs,
                     key=jax.random.PRNGKey(7))

    # --- production: one wire round per step, slots from the same sampler --
    agg = CompressedAggregation(method="diana_rr", wire="shared",
                                fraction=1.0, alpha=alpha, n_slots=n,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=gamma, remat=False, seq_shard=False)
    stream = make_batch_stream(
        {"tokens": tokens.astype(np.int32)}, sampler, prefetch=False)
    with jax.set_mesh(mesh), stream:
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m, lr=gamma,
                                   mesh=mesh), shardings)
        for t in range(epochs * n):
            slots = jnp.asarray(shared_slots_for_step(sampler, t))
            state, _ = jitted(state, next(stream), jax.random.key(3), slots)

    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(sim.params),
            jax.tree_util.tree_leaves_with_path(state.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-4, rtol=2e-3, err_msg=str(pa))
    # slot-selection coherence: the (M, n_slots, *param) tables themselves
    # match — the wire touched exactly the slots the pipeline ordered. The
    # tables integrate raw per-round gradients (no 1/M averaging), so they
    # carry more reduction-order float noise than the params; a wrong slot
    # would show up as O(0.1) row-level differences, not 1e-3 ripples.
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(sim.shifts),
            jax.tree_util.tree_leaves_with_path(state.shifts)):
        assert a.shape == b.shape, (pa, a.shape, b.shape)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-3, rtol=5e-2, err_msg=str(pa))


def test_per_slot_untouched_slots_stay_zero(mesh_4x2):
    """Two rounds into a 4-slot table only the two visited rows move."""
    from repro.launch.steps import configure_agg

    agg = configure_agg(
        CompressedAggregation(method="diana_rr", wire="shared", fraction=1.0,
                              n_slots=4, shift_dtype=jnp.float32), mesh_4x2)
    specs = _wire_specs(mesh_4x2, GRADS)
    visited = (2, 0)

    def body(g):
        g = jax.tree.map(lambda x: x[0], g)
        state = agg.init(g)
        key = jax.random.PRNGKey(0)
        for t, s in enumerate(visited):
            _, state = agg.aggregate(g, state, jax.random.fold_in(key, t),
                                     slot=jnp.int32(s))
        return jax.tree.map(lambda x: x[None], state.shifts)

    out_specs = jax.tree.map(
        lambda s: P(s[0], None, *s[1:]), _wire_specs(mesh_4x2, GRADS))
    shifts = jax.jit(_shard_map(body, mesh_4x2, (specs,), out_specs))(GRADS)
    for k in GRADS:
        table = np.asarray(shifts[k])  # (M, n_slots, ...)
        for s in range(4):
            touched = (np.abs(table[:, s]) > 0).any()
            assert touched == (s in visited), (k, s)


# ---------------------------------------------------------------------------
# simulator-vs-pod cross-check: the production NASTYA step inherits the
# simulator's (already theorem-tested) semantics
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("stablelm-1.6b"), seq=8)
    return dataclasses.replace(cfg, dtype=jnp.float32)


@pytest.mark.parametrize("name", ["q_nastya", "diana_nastya"])
def test_pod_nastya_matches_simulator(name, mesh_4x2):
    """`q_nastya`/`diana_nastya` from core/algorithms.py and the pod-level
    NASTYA step produce the same trajectory on a tiny problem: 4 clients
    (each its own pod on the flat mesh — paper Algorithms 4-5 exactly),
    full-batch (every local micro-batch identical, so the RR orders of the
    two implementations cannot diverge), fraction=1.0 (both compressors are
    exact at k=d, so the different Rand-k samplers coincide), same gamma/
    eta/alpha. The production wire must inherit the simulator's semantics.
    """
    from repro.core.algorithms import init_algorithm, make_epoch_fn, ALGORITHMS
    from repro.compression.ops import RandK
    from repro.launch import steps
    from repro.launch.mesh import num_clients
    from repro.models import transformer

    cfg = _tiny_cfg()
    mesh = mesh_4x2
    m = num_clients(mesh)
    local_steps = 3
    gamma, eta, alpha = 0.02, 0.05, 0.5
    seq = 8

    # one full-batch of tokens per client, repeated local_steps times
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, size=(m, 1, seq + 1))  # (M, b=1, S+1)
    sim_data = {"tokens": jnp.asarray(
        np.broadcast_to(tokens[:, None], (m, local_steps, 1, seq + 1)).copy(),
        jnp.int32)}  # (M, n, b, S+1)

    loss_fn = lambda p, b: transformer.loss_fn(p, b, cfg, remat=False,
                                               seq_shard=False)
    params0 = transformer.init_params(jax.random.key(0), cfg)

    # --- simulator epochs ---------------------------------------------------
    spec, epoch = make_epoch_fn(name, loss_fn, RandK(fraction=1.0),
                                gamma=gamma, eta=eta, alpha=alpha)
    sim = init_algorithm(ALGORITHMS[name], params0, m, local_steps)
    ep = jax.jit(epoch)
    for e in range(2):
        sim = ep(sim, sim_data, jax.random.PRNGKey(10 + e))

    # --- production pod step ------------------------------------------------
    method = "diana" if name == "diana_nastya" else "q"
    agg = CompressedAggregation(method=method, wire="shared", fraction=1.0,
                                alpha=alpha, pod_alpha=alpha,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=gamma, eta=eta, local_steps=local_steps,
        remat=False, seq_shard=False)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m, lr=gamma,
                                   mesh=mesh, local_steps=local_steps),
            shardings)
        # client-major rows, local_steps identical micro-batches per client
        batch = {"tokens": jnp.asarray(
            np.repeat(tokens[:, 0], local_steps, axis=0), jnp.int32)}
        for e in range(2):
            state, _ = jitted(state, batch, jax.random.key(10 + e))

    # the two implementations compute identical math but with different
    # reduction orders (single-device simulator vs 8-way sharded step);
    # float noise grows chaotically along the trajectory — after 2 epochs
    # the parameter updates are O(1e-2) and the divergence O(5e-5) (<1%)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(sim.params),
            jax.tree_util.tree_leaves_with_path(state.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-4, rtol=2e-3, err_msg=str(pa))


# ---------------------------------------------------------------------------
# NASTYA on the two-level mesh: runs and trains
# ---------------------------------------------------------------------------

def test_nastya_two_pod_step_trains(mesh_2x2x2):
    """End-to-end: 2 pods x 2 clients, 2 local RR mini-epochs per round,
    DIANA at both levels — loss decreases over a few rounds."""
    from repro.configs import get_config, reduced
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    cfg = reduced(get_config("stablelm-1.6b"), seq=8)
    mesh = mesh_2x2x2
    m = num_clients(mesh)
    local_steps = 2
    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.5,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, _ = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.05, eta=0.2, local_steps=local_steps,
        remat=False, seq_shard=False)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m, mesh=mesh,
                                   local_steps=local_steps), shardings)
        batch = {"tokens": jax.random.randint(
            jax.random.key(1), (m * local_steps * 2, 9), 0, cfg.vocab)}
        losses = []
        for _ in range(10):
            state, metrics = jitted(state, batch, jax.random.key(2))
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.05, losses


def test_nastya_two_pod_diana_rr_trains(mesh_2x2x2):
    """Acceptance: `CompressedAggregation(method="diana_rr")` on the 2-pod
    NASTYA mesh — per-slot shifts on the intra-pod wire (slots riding the
    per-pod micro-epoch permutation), single-shift row 0 on the inter-pod
    epoch gradient — trains."""
    from repro.configs import get_config, reduced
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    cfg = reduced(get_config("stablelm-1.6b"), seq=8)
    mesh = mesh_2x2x2
    m = num_clients(mesh)
    local_steps = 2
    agg = CompressedAggregation(method="diana_rr", wire="shared",
                                fraction=0.5, n_slots=local_steps,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, _ = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.05, eta=0.2, local_steps=local_steps,
        remat=False, seq_shard=False)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m, mesh=mesh,
                                   local_steps=local_steps), shardings)
        batch = {"tokens": jax.random.randint(
            jax.random.key(1), (m * local_steps * 2, 9), 0, cfg.vocab)}
        slots = jnp.arange(local_steps, dtype=jnp.int32)
        losses = []
        for _ in range(10):
            state, metrics = jitted(state, batch, jax.random.key(2), slots)
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.05, losses
        # both levels hold slot tables; the inner level saw both slots
        sh = np.asarray(jax.tree.leaves(state.shifts)[0])
        assert sh.shape[1] == local_steps
        assert (np.abs(sh) > 0).any(axis=tuple(range(2, sh.ndim))).all(), \
            "every (client, slot) table row should have been touched"
