"""Compression backend: one dispatch layer for every compress / decompress /
shift-update in the repo (DESIGN.md §3.5).

Two backends implement the same primitives:

``reference``
    Pure-jnp implementations (`repro.kernels.ref` plus the vectorized mask
    formula). The semantics oracle — every pallas result must match it to
    atol 1e-6 (f32), enforced by tests/test_kernels.py.

``pallas``
    The Pallas kernels in `repro.kernels`: Mosaic on TPU, interpret mode on
    CPU. One kernel launch covers the whole flat buffer — the simulator
    ravels each client's gradient pytree once and compresses all M clients
    in a single call, and the pod wire's circular row-block gather/scatter
    runs as `k_blocks` VMEM copies instead of a `jnp.roll` of the full leaf.

Consumers:

- `repro.core.algorithms` routes per-client compression and the shift-rule
  updates (repro.core.rules) through `compress_clients` / `tree_diana_shift`;
- `repro.core.dist` routes the shared wire through `wire_exchange` /
  `exchange_slab` and the Rand-block kernels: `wire_compress` /
  `wire_decompress` on the dense-leaf paths ('q', 'ef'),
  `wire_decompress_into` (the in-place window write-back) on the
  window-sparse DIANA path;
- `benchmarks/compression_bench.py` times both backends against the seed
  per-leaf `jax.random.choice` path and writes BENCH_compression.json.

Backend selection: pass a name explicitly, or set REPRO_COMPRESSION_BACKEND
(default "pallas" — on CPU the kernels run in interpret mode, which lowers
to the same XLA ops as the reference but keeps the TPU path exercised).

Operator semantics on the batched paths (all Assumption-1 compliant):

- Rand-k is the circular-window sampler over the raveled tree (marginal
  inclusion probability exactly k/d -> unbiased, omega = d/k - 1 exact).
- QSGD is the TPU-native blockwise variant: per-1024-tile max-abs scale
  instead of the global L2 norm (kernels/qsgd.py). Unbiased conditional on
  the tile scales. The leaf-level `QSGDQuantizer.compress` API keeps the
  paper-exact global-norm semantics.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.diana_shift import LANES
from repro.kernels.pack import pack_slab, unpack_reduce, unpack_slab
from repro.kernels.qsgd import TILE, qsgd_quantize
from repro.kernels.randk import (
    BLOCK_ROWS,
    randk_compress,
    randk_decompress,
    randk_decompress_into,
    randk_mask,
)
from repro.kernels.ops import diana_shift as _pallas_diana_shift

# Re-exported kernel geometry: BLOCK_ROWS is the row-block granularity every
# wire-level Rand-k draw is quantized to. Consumers (repro.core.dist) import
# it from here — this module owns the stable kernel surface; reaching into
# repro.kernels directly is a lint error (rule `kernel-import`).
__all__ = ["BLOCK_ROWS", "LANES", "TILE", "WIRE_DTYPES", "get_backend"]

# Wire transport formats for the shared wire's slab (core.dist validates the
# method/wire combinations; this module owns the mechanics). 'f32' is the
# status-quo psum; 'bf16' downcasts the value slab before the psum; the
# packed modes move a byte lattice + f32 scale sideband via all_gather and a
# fused unpack-reduce (kernels/pack.py, DESIGN.md §3.13).
WIRE_DTYPES = ("f32", "bf16", "packed8", "packed4")

BACKENDS = ("reference", "pallas")
_ENV_VAR = "REPRO_COMPRESSION_BACKEND"

# flat buffers are padded to the coarsest alignment any kernel needs so one
# padded layout serves qsgd (TILE=1024) and the mask kernel (8*128=1024)
_ALIGN = TILE


def tree_ravel_clients(tree):
    """Ravel a client-stacked pytree (leaves (M, *s)) into one (M, D) buffer.

    Returns (mat, unravel). unravel(mat) restores per-leaf shapes/dtypes.
    """
    leaves, treedef = jax.tree.flatten(tree)
    m = leaves[0].shape[0]
    sizes = [int(np.prod(leaf.shape[1:])) for leaf in leaves]
    shapes = [leaf.shape for leaf in leaves]
    dtypes = [leaf.dtype for leaf in leaves]
    offsets = np.cumsum([0] + sizes)
    mat = jnp.concatenate(
        [jnp.reshape(leaf, (m, -1)).astype(jnp.float32) for leaf in leaves],
        axis=1,
    )

    def unravel(out):
        parts = [
            jnp.reshape(out[:, offsets[i]:offsets[i + 1]], shapes[i]).astype(dtypes[i])
            for i in range(len(sizes))
        ]
        return jax.tree.unflatten(treedef, parts)

    return mat, unravel


def _pad_cols(mat: jax.Array, multiple: int) -> jax.Array:
    pad = (-mat.shape[1]) % multiple
    if pad:
        mat = jnp.pad(mat, ((0, 0), (0, pad)))
    return mat


@dataclasses.dataclass(frozen=True)
class CompressionBackend:
    """Static dispatch between the jnp reference and the Pallas kernels."""

    name: str = "pallas"
    interpret: bool | None = None  # None -> auto (interpret on CPU)

    def __post_init__(self):
        if self.name not in BACKENDS:
            raise ValueError(f"unknown backend {self.name!r}; options: {BACKENDS}")

    @property
    def is_pallas(self) -> bool:
        return self.name == "pallas"

    # -- flat batched primitives ----------------------------------------------

    def randk_dense(self, mat: jax.Array, starts: jax.Array, *, d: int,
                    k: int) -> jax.Array:
        """Dense Q(x) for M clients: circular window mask + (d/k) scale.

        mat: (M, Dp) with Dp 1024-aligned and d <= Dp the real flat length.
        """
        if self.is_pallas:
            return randk_mask(mat, starts, d=d, k=k, interpret=self.interpret)
        return ref.randk_mask_ref(mat, starts, d=d, k=k)

    def qsgd_dense(self, mat: jax.Array, u: jax.Array, *, levels: int) -> jax.Array:
        """Blockwise-QSGD quantize->dequantize; mat (M, Dp), Dp % TILE == 0."""
        m, dp = mat.shape
        flat, uf = mat.reshape(m * dp), u.reshape(m * dp)
        if self.is_pallas:
            out = qsgd_quantize(flat, uf, levels=levels, interpret=self.interpret)
        else:
            out = ref.qsgd_quantize_ref(flat, uf, levels=levels, tile=TILE)
        return out.reshape(m, dp)

    def diana_shift_flat(self, h, q_own, mh, q_mean, *, alpha: float,
                         beta: float | None = None):
        """Fused DIANA update on flat (N,) buffers -> (direction, h', H').

        `beta` is the mean-shift stepsize (H' = H + beta*Q_mean); defaults to
        alpha. Cohort-sampled fleets pass beta = (M/C)*alpha (DESIGN.md §3.10).
        """
        if self.is_pallas:
            return _pallas_diana_shift(h, q_own, mh, q_mean, alpha=alpha,
                                       beta=beta)
        return ref.diana_shift_update_ref(h, q_own, mh, q_mean, alpha, beta)

    # -- pytree entry points (the simulator hot path) -------------------------

    def compress_clients(self, comp, key: jax.Array, tree):
        """Q(g_m) for all M clients of a client-stacked pytree in ONE launch.

        Ravel once -> compress once -> unravel: the per-leaf Python loop and
        the per-leaf PRNG sorts of the seed path collapse into a single flat
        buffer operation over the (M, D) matrix of client gradients.
        """
        from repro.compression.ops import Identity, QSGDQuantizer, RandK

        if isinstance(comp, Identity):
            return tree
        m = jax.tree.leaves(tree)[0].shape[0]
        mat, unravel = tree_ravel_clients(tree)
        d = mat.shape[1]
        if isinstance(comp, RandK):
            k = comp._k(d)
            starts = jax.random.randint(key, (m,), 0, d)  # independent/client
            dense = self.randk_dense(_pad_cols(mat, _ALIGN), starts, d=d, k=k)
            return unravel(dense[:, :d])
        if isinstance(comp, QSGDQuantizer):
            padded = _pad_cols(mat, _ALIGN)
            u = jax.random.uniform(key, padded.shape)
            dense = self.qsgd_dense(padded, u, levels=comp.levels)
            return unravel(dense[:, :d])
        # generic operators (TopK, NaturalCompression, user-defined): still a
        # single ravel; the operator itself runs once per client under vmap.
        keys = jax.random.split(key, m)
        dense = jax.vmap(comp.compress)(keys, mat)
        return unravel(dense)

    def tree_diana_shift(self, h_tree, qo_tree, mh_tree, qm_tree, *,
                         alpha: float, beta: float | None = None):
        """Fused DIANA update over whole pytrees (same structure/shapes).

        Returns (direction_tree, h_tree', mh_tree'). On the pallas backend
        this is ONE kernel launch over the raveled buffer — each input reads
        HBM once and the three outputs write in the same pass, vs five
        param-sized round-trips for three separate tree_maps. The reference
        backend stays per-leaf (no ravel copies) and is the semantics oracle.
        """
        if self.is_pallas:
            from repro.compression.ops import tree_ravel

            h, unravel = tree_ravel(h_tree)
            qo, _ = tree_ravel(qo_tree)
            mh, _ = tree_ravel(mh_tree)
            qm, _ = tree_ravel(qm_tree)
            direction, h_new, mh_new = self.diana_shift_flat(h, qo, mh, qm,
                                                             alpha=alpha,
                                                             beta=beta)
            return unravel(direction), unravel(h_new), unravel(mh_new)
        h_leaves, treedef = jax.tree.flatten(h_tree)
        trips = [
            ref.diana_shift_update_ref(a, b, c, d, alpha, beta)
            for a, b, c, d in zip(h_leaves, jax.tree.leaves(qo_tree),
                                  jax.tree.leaves(mh_tree),
                                  jax.tree.leaves(qm_tree))
        ]
        return tuple(
            jax.tree.unflatten(treedef, [t[i] for t in trips]) for i in range(3)
        )

    # -- wire primitives (the pod shared-seed Rand-block collective) ----------

    def wire_exchange(self, rows: jax.Array, start_block: jax.Array, *,
                      k_blocks: int, block_rows: int,
                      axes: tuple[str, ...], weight: jax.Array | None = None,
                      wire_dtype: str = "f32", levels: int | None = None,
                      quant_u: jax.Array | None = None):
        """One level of the (possibly hierarchical) shared wire on a dense
        leaf: circular gather of the k-row slab (`wire_compress`), then the
        sparse collective over `axes` (`exchange_slab`).

        Returns (own_vals, mean_vals). The dense-leaf paths of the shared
        wire ('q', 'ef') land here; the window-sparse DIANA path of
        `core.dist` gathers its own slab and calls `exchange_slab` directly.
        """
        vals = self.wire_compress(rows, start_block, k_blocks=k_blocks,
                                  block_rows=block_rows)
        return self.exchange_slab(vals, axes=axes, weight=weight,
                                  wire_dtype=wire_dtype, levels=levels,
                                  quant_u=quant_u)

    def exchange_slab(self, vals: jax.Array, *, axes: tuple[str, ...],
                      weight: jax.Array | None = None,
                      wire_dtype: str = "f32", levels: int | None = None,
                      quant_u: jax.Array | None = None):
        """The sparse collective of one wire level on an already gathered
        (K, D) f32 slab. Returns (own_vals, mean_vals).

        This is the per-level dispatch point: the intra-pod ("data") and
        inter-pod ("pod") exchanges both land here, each with its own
        window, so only the compressed slab ever crosses either wire. Must
        run inside a shard_map whose manual axes include `axes`.

        `weight` (per-rank scalar, pre-normalized so an all-ones cohort gives
        exactly 1.0) scales this rank's contribution to the collective mean —
        the buffered-async / elastic-masking hook. Own vals stay unweighted so
        local shift updates use the client's actual message.

        Transport (`wire_dtype`, DESIGN.md §3.13):

        'f32'      the status quo: psum the value slab. With `levels` set the
                   slab is first quantized through the SAME pack->unpack pair
                   the packed modes use — the bit-match reference for them,
                   and a QSGD-on-the-wire mode in its own right.
        'bf16'     psum the slab at bf16 (2 B/lane, lossy); own vals are the
                   bf16 round-trip so shift updates see what the wire moved.
        'packed8'  quantize (levels <= 127) and all_gather the biased byte
                   lattice + f32 per-row scale sideband, then ONE fused
                   unpack-accumulate kernel forms the mean (a psum of packed
                   ints would be wrong — scales are per rank). Elastic
                   weights fold into the scale sideband, so no extra
                   collective; q_own decodes this rank's own slab with the
                   UNWEIGHTED scale.
        'packed4'  same, two rows per byte (levels <= 7).

        `quant_u` are the shared stochastic-rounding uniforms (slab-shaped),
        drawn by the caller from the level key + WIRE_QUANT_SALT; required
        iff `levels` is set.
        """
        if wire_dtype in ("packed8", "packed4"):
            nib = wire_dtype == "packed4"
            packed, scales = self.pack_slab(vals, quant_u, levels=levels,
                                            nibble=nib)
            own = self.unpack_slab(packed, scales, levels=levels,
                                   n_rows=vals.shape[0], nibble=nib)
            wscales = scales if weight is None else scales * weight
            gathered_p = jax.lax.all_gather(packed, axes)
            gathered_s = jax.lax.all_gather(wscales, axes)
            mean = self.unpack_reduce(gathered_p, gathered_s, levels=levels,
                                      n_rows=vals.shape[0], nibble=nib)
            return own, mean
        if levels is not None:
            # f32 transport of the quantized payload: round-trip through the
            # pack kernels so every byte/scale is bitwise identical to what
            # the packed transport would move (the lossless-levels argument)
            packed, scales = self.pack_slab(vals, quant_u, levels=levels)
            vals = self.unpack_slab(packed, scales, levels=levels,
                                    n_rows=vals.shape[0])
        if wire_dtype == "bf16":
            own = vals.astype(jnp.bfloat16).astype(jnp.float32)
            shared = own if weight is None else own * weight
            mean = jax.lax.pmean(shared.astype(jnp.bfloat16), axes)
            return own, mean.astype(jnp.float32)
        shared = vals if weight is None else vals * weight
        return vals, jax.lax.pmean(shared, axes)

    def pack_slab(self, vals: jax.Array, u: jax.Array, *, levels: int,
                  nibble: bool = False):
        """Quantize + bit-pack a wire slab -> (packed uint8, f32 scales)."""
        if self.is_pallas:
            return pack_slab(vals, u, levels=levels, nibble=nibble,
                             interpret=self.interpret)
        return ref.pack_slab_ref(vals, u, levels=levels, nibble=nibble,
                                 block_rows=BLOCK_ROWS)

    def unpack_slab(self, packed: jax.Array, scales: jax.Array, *,
                    levels: int, n_rows: int, nibble: bool = False):
        """Decode one packed slab back to (n_rows, D) f32 values."""
        if self.is_pallas:
            return unpack_slab(packed, scales, levels=levels, n_rows=n_rows,
                               nibble=nibble, interpret=self.interpret)
        return ref.unpack_slab_ref(packed, scales, levels=levels,
                                   n_rows=n_rows, nibble=nibble)

    def unpack_reduce(self, packed: jax.Array, scales: jax.Array, *,
                      levels: int, n_rows: int, nibble: bool = False):
        """All-gathered packed slabs + scales -> fused f32 mean slab."""
        if self.is_pallas:
            return unpack_reduce(packed, scales, levels=levels, n_rows=n_rows,
                                 nibble=nibble, interpret=self.interpret)
        return ref.unpack_reduce_ref(packed, scales, levels=levels,
                                     n_rows=n_rows, nibble=nibble)

    def wire_compress(self, rows: jax.Array, start_block: jax.Array, *,
                      k_blocks: int, block_rows: int) -> jax.Array:
        """(N, D) rows -> (k_blocks*block_rows, D) circular gather + scale."""
        if self.is_pallas:
            return randk_compress(rows, start_block, k_blocks=k_blocks,
                                  block_rows=block_rows,
                                  interpret=self.interpret)
        return ref.randk_compress_ref(rows, start_block, k_blocks=k_blocks,
                                      block_rows=block_rows)

    def wire_decompress(self, vals: jax.Array, start_block: jax.Array, *,
                        n_rows: int, block_rows: int) -> jax.Array:
        """(K, D) vals -> (n_rows, D) zero-padded circular scatter."""
        if self.is_pallas:
            return randk_decompress(vals, start_block, n_rows=n_rows,
                                    block_rows=block_rows,
                                    interpret=self.interpret)
        return ref.randk_decompress_ref(vals, start_block, n_rows=n_rows,
                                        block_rows=block_rows)

    def wire_decompress_into(self, into: jax.Array, vals: jax.Array,
                             start_block: jax.Array, base_block: jax.Array, *,
                             n_rows: int, block_rows: int) -> jax.Array:
        """(K, D) vals written over the circular window of the n_rows-row
        segment at block `base_block` of `into` (R, D); the rest is kept.
        On the pallas backend the output aliases `into` (in place)."""
        if self.is_pallas:
            return randk_decompress_into(into, vals, start_block, base_block,
                                         n_rows=n_rows, block_rows=block_rows,
                                         interpret=self.interpret)
        return ref.randk_decompress_into_ref(into, vals, start_block,
                                             base_block, n_rows=n_rows,
                                             block_rows=block_rows)


def get_backend(name: str | CompressionBackend | None = None) -> CompressionBackend:
    """Resolve a backend: explicit arg > $REPRO_COMPRESSION_BACKEND > pallas."""
    if isinstance(name, CompressionBackend):
        return name
    if name is None:
        name = os.environ.get(_ENV_VAR, "pallas")
    return CompressionBackend(name=name)
