"""Production compressed-gradient aggregation for TPU pods.

This is the paper's communication layer rethought for ICI collectives
(DESIGN.md §3). Two wire modes:

``independent`` (paper-exact semantics)
    Every client Rand-k-compresses its own gradient with an *independent*
    key (paper Assumption 1 + the 1/M variance factor in Theorems 1-2), then
    the results are averaged with a dense ``psum``. On TPU the zeros travel
    too — the collective term does not shrink; this is the faithful baseline
    recorded in EXPERIMENTS.md §Perf.

``shared`` (TPU-native sparse collective — beyond-paper optimization)
    All clients draw the *same* coordinate block per round (shared PRNG
    seed). Then only the k selected values are psum'd: collective bytes drop
    by d/k (~50x at the paper's k/d≈0.02). Coordinates are a contiguous
    random block of whole 8-row groups ("Rand-block", DESIGN.md §3.2):
    uniform marginal inclusion probability k/d gives exactly the Rand-k
    variance bound omega = d/k - 1 (the second moment only needs marginals),
    while the gather/scatter runs through the Pallas circular row-block
    kernels (`repro.kernels.randk`) dispatched by the compression backend
    (DESIGN.md §3.5) — k_blocks sequential VMEM copies driven by one
    prefetched scalar, instead of a `jnp.roll` of the full leaf. Because
    coordinates are shared, mean_m Q(d_m) == Q(mean_m d_m): the omega/M
    factor of the paper becomes omega applied to the already-averaged vector
    — still Assumption-1 compliant per round, and with DIANA shifts the
    compressed residual d_m -> 0 so the fixed point is unchanged (Theorem 2
    logic carries over).

Two-level (pod) hierarchy (DESIGN.md §3.6):

    When `pod_axes` is non-empty the wire is HIERARCHICAL. The inner level
    runs the exchange above over `client_axes` (the ranks inside one pod,
    fast ICI); the outer level runs a second, *independently keyed*
    compressed exchange over `pod_axes` (the slow inter-pod links), applied
    to the inner level's output. DIANA shifts exist at both levels
    (`DianaState.shifts/mean_shift` inner, `pod_shifts/pod_mean_shift`
    outer), so both compressed residuals -> 0 and the fixed point is still
    the exact mean. The composed operator is unbiased with second moment
    (1+omega_1)(1+omega_2)||x||^2 (tower rule over the two independent
    draws). With a single pod (`pod_size == 1`) there is no inter-pod link,
    so the outer exchange degrades to the identity — the two-level wire
    bit-matches the flat wire (tests/test_pod_wire.py parity test).

    `client_axes=()` is also allowed: each outer rank is a pod of one
    client, which is exactly the paper's Algorithms 4-5 layout when the
    launch layer maps NASTYA local epochs onto the mesh (launch/steps.py).

Aggregation methods (paper Secs. 2.1-2.2, production variants). The
shift/memory arithmetic of every method lives in ONE place — the shift-rule
layer (`repro.core.rules`, DESIGN.md §3.8) shared with the simulator; this
module only owns the wire (compression geometry, collectives, key derivation):

- ``dense``     plain mean gradient (no compression) — sanity baseline
- ``q``         Q-RR-style: direction = mean_m Q(g_m)           (NoShift)
- ``diana``     DIANA with one shift per client               (SingleShift):
                    direction = H_t + mean_m Q(g_m - h_m)
                    h_m   += alpha * Q(g_m - h_m)
                    H_t+1  = H_t + alpha * mean_m Q(g_m - h_m)
- ``diana_rr``  DIANA-RR (paper Algorithm 3) with an n_slots-entry shift
                table per rank (PerSlotShift): the round's shared batch
                index selects which control variate the exchange reads and
                updates. Requires every rank of a wire level on the SAME
                slot per round — the `rr_shared` sampler order; see the
                slot-semantics note in repro/core/rules.py.
- ``ef``        error feedback (EfRule): memory is the compression residual
                e_m; the wire sends the CONTRACTIVE (unscaled) Rand-block
                window of g_m + e_m and keeps what it dropped.

All functions are designed to run INSIDE a `shard_map` body whose manual axes
include the client/pod axes; gradients arrive as this device's local block of
the parameter pytree, and `lax.pmean` over the level's axes is the server.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.compression.backend import BLOCK_ROWS, WIRE_DTYPES, get_backend
from repro.core.rules import WIRE_RULES, ShiftRule
from repro.core.salts import POD_KEY_SALT, WIRE_QUANT_SALT

# Biased-byte representation caps: 2*levels + 1 distinct lattice points must
# fit the lane (256 byte values / 16 nibble values) — the lossless-levels
# bound of DESIGN.md §3.13. Packed wires default to the largest level count
# their lane can carry losslessly.
_WIRE_LEVEL_CAPS = {"packed8": 127, "packed4": 7}


def payload_itemsize(wire_dtype: str, rule: ShiftRule,
                     leaf_dtype=jnp.float32) -> float:
    """Bytes per slab element one rank puts on the shared wire.

    The single accounting authority for the wire's transport width — dist's
    `wire_bytes_per_round`, the fleet driver's bit charging, and the jaxpr
    census all derive from it, so the three byte accountings cannot drift.

    f32 transport: stateful rules (diana/diana_rr/ef) psum f32 payloads; the
    memory-free 'q' slabs travel at leaf dtype. bf16 halves the lane. The
    packed modes move one byte per element (packed8) or one byte per TWO
    row-paired elements (packed4 -> 0.5); their f32 per-row scale sideband
    is accounted separately (`scale_sideband_bytes`).
    """
    if wire_dtype == "bf16":
        return 2
    if wire_dtype == "packed8":
        return 1
    if wire_dtype == "packed4":
        return 0.5
    return 4 if rule.has_shifts else jnp.dtype(leaf_dtype).itemsize


def scale_sideband_bytes(wire_dtype: str, slab_rows: int) -> int:
    """Bytes of the packed wire's f32 per-row scale sideband (0 otherwise)."""
    if wire_dtype in _WIRE_LEVEL_CAPS:
        return 4 * slab_rows
    return 0


class DianaState(NamedTuple):
    """Per-device compression state (local blocks of param-shaped trees).

    `shifts`/`mean_shift` are the inner (intra-pod) level: h_m per client
    rank and their per-pod running mean. `pod_shifts`/`pod_mean_shift` are
    the outer (inter-pod) level: one shift per pod and the global mean.
    Unused levels hold None (flat wire: pod_* is None; pod-granular NASTYA
    with `client_axes=()`: the inner pair is None).

    Layout depends on the method's shift rule: 'diana' keeps param-shaped
    leaves; 'diana_rr' prepends an `n_slots` axis to every table (the
    round's slot indexes it); 'ef' keeps the residual in `shifts` only
    (mean tables stay None — error feedback has no server memory).
    """

    shifts: Any  # h_m: this client's shift (differs across client_axes)
    mean_shift: Any  # H_t = (1/M) sum_m h_m (identical within a pod)
    pod_shifts: Any = None  # h_p: this pod's shift (differs across pod_axes)
    pod_mean_shift: Any = None  # (1/P) sum_p h_p (identical everywhere)


@dataclasses.dataclass(frozen=True)
class CompressedAggregation:
    """Config + pure functions for the production gradient wire."""

    method: str = "diana"  # 'dense' | 'q' | 'diana' | 'diana_rr' | 'ef'
    wire: str = "shared"  # 'shared' | 'independent'
    fraction: float = 0.02  # k/d on the intra-pod (inner) wire
    alpha: float | None = None  # shift stepsize; None -> 1/(1+omega) (Thm 2)
    shift_dtype: Any = jnp.bfloat16
    n_slots: int = 1  # per-slot shift-table rows ('diana_rr': the data n)
    client_axes: tuple[str, ...] = ("data",)  # inner level (ranks in a pod)
    pod_axes: tuple[str, ...] = ()  # outer level; () = flat single-level wire
    pod_size: int = 1  # static product of pod_axes sizes (1 = no inter-pod link)
    pod_fraction: float | None = None  # inter-pod k/d; None -> `fraction`
    pod_alpha: float | None = None  # pod shift stepsize; None -> 1/(1+omega_pod)
    pod_slots: int | None = None  # outer-level slot rows; None -> n_slots.
    # configure_agg sets 1 on NASTYA paths: the inter-pod exchange carries
    # the slot-free epoch gradient, so rows past 0 would never be touched.
    mean_scale: float = 1.0  # mean-shift stepsize scale: beta = mean_scale *
    # alpha at the client-granular level. Cohort-sampled fleets set M/C so
    # the resident mean shift tracks the population mean h_bar instead of
    # (C/M)*h_bar (DESIGN.md §3.10); 1.0 = the paper's full-participation form.
    backend: str | None = None  # 'reference' | 'pallas' | None (env/default)
    wire_dtype: str = "f32"  # slab transport: 'f32'|'bf16'|'packed8'|'packed4'
    # (applies to BOTH wire levels; DESIGN.md §3.13). 'f32' + wire_levels=None
    # is the bitwise status quo.
    wire_levels: int | None = None  # stochastic-quantization levels for the
    # slab (None -> unquantized f32/bf16; packed modes default to their lane
    # cap: 127 for packed8, 7 for packed4). Orthogonal to wire_dtype: 'f32'
    # with levels set moves the SAME quantized payload at 4 B/lane — the
    # bit-match reference for the packed transports.

    def __post_init__(self):
        if self.method not in WIRE_RULES:
            raise ValueError(f"unknown method {self.method!r}; options: "
                             f"{sorted(WIRE_RULES)}")
        if self.n_slots < 1:
            raise ValueError(f"n_slots={self.n_slots}")
        if self.pod_slots is not None and self.pod_slots < 1:
            raise ValueError(f"pod_slots={self.pod_slots}")
        if self.wire_dtype not in WIRE_DTYPES:
            raise ValueError(f"unknown wire_dtype {self.wire_dtype!r}; "
                             f"options: {WIRE_DTYPES}")
        if self.wire_dtype != "f32" or self.wire_levels is not None:
            if self.method == "dense":
                raise ValueError(
                    "method 'dense' has no compressed slab; wire_dtype must "
                    "stay 'f32' with wire_levels=None")
            if self.wire != "shared":
                raise ValueError(
                    "bf16/packed/quantized transport needs the shared wire "
                    f"(wire={self.wire!r} moves dense leaves, not slabs)")
        if self.wire_dtype == "bf16" and self.wire_levels is not None:
            raise ValueError(
                "wire_levels with bf16 transport is ambiguous (quantize to a "
                "lattice, then round the lattice to bf16?) — pick one of "
                "'f32'+levels (QSGD wire) or plain 'bf16'")
        cap = _WIRE_LEVEL_CAPS.get(self.wire_dtype)
        if self.wire_levels is not None:
            if self.wire_levels < 1:
                raise ValueError(f"wire_levels={self.wire_levels}")
            if cap is not None and self.wire_levels > cap:
                raise ValueError(
                    f"wire_levels={self.wire_levels} overflows the "
                    f"{self.wire_dtype} lane: 2*levels+1 lattice points must "
                    f"fit, so levels <= {cap}")

    @property
    def _quant_levels(self) -> int | None:
        """Effective quantization level count (packed lanes default full)."""
        if self.wire_levels is not None:
            return self.wire_levels
        return _WIRE_LEVEL_CAPS.get(self.wire_dtype)

    @property
    def _pod_slots(self) -> int:
        return self.n_slots if self.pod_slots is None else self.pod_slots

    @property
    def rule(self) -> ShiftRule:
        """The method's shift rule — the single source of shift semantics
        (shared with the simulator drivers; repro.core.rules)."""
        return WIRE_RULES[self.method]

    # -- state ---------------------------------------------------------------

    def init(self, local_params) -> DianaState | None:
        rule = self.rule
        if not rule.has_shifts:
            return None
        inner = bool(self.client_axes)
        outer = bool(self.pod_axes)
        mk = lambda ns: rule.init_shifts(local_params, n_slots=ns,
                                         dtype=self.shift_dtype)
        return DianaState(
            shifts=mk(self.n_slots) if inner else None,
            mean_shift=mk(self.n_slots) if inner and rule.has_mean else None,
            pod_shifts=mk(self._pod_slots) if outer else None,
            pod_mean_shift=mk(self._pod_slots) if outer and rule.has_mean
            else None,
        )

    def omega(self) -> float:
        if self.method == "dense":
            return 0.0
        return 1.0 / self.fraction - 1.0

    def pod_omega(self) -> float:
        if self.method == "dense" or self.pod_size == 1:
            return 0.0
        f = self.fraction if self.pod_fraction is None else self.pod_fraction
        return 1.0 / f - 1.0

    @property
    def shift_lr(self) -> float:
        """alpha <= 1/(1+omega) (Theorem 2 / 4 condition)."""
        if self.alpha is not None:
            return self.alpha
        return 1.0 / (1.0 + self.omega())

    @property
    def pod_shift_lr(self) -> float:
        if self.pod_alpha is not None:
            return self.pod_alpha
        return 1.0 / (1.0 + self.pod_omega())

    @property
    def _pod_fraction(self) -> float:
        return self.fraction if self.pod_fraction is None else self.pod_fraction

    # -- per-leaf compression primitives --------------------------------------
    #
    # Compression operates on a ROW view of each leaf: (prod(shape[:-1]),
    # shape[-1]). The last axis is the tensor-parallel ("model") sharded axis
    # in every weight layout (DESIGN.md §5), so selecting whole rows never
    # reshards a leaf — the sparse collective runs directly on model-sharded
    # row slabs. Row selection is uniform, so the operator stays unbiased
    # with omega = n_rows/k_rows - 1 = 1/fraction - 1 (block-granular Rand-k).

    @staticmethod
    def _row_view(leaf, lead: int = 0):
        """(rows, cols) view of a leaf; `lead` leading axes (a shift table's
        slot axis) stay in front: (S, rows, cols)."""
        shape = leaf.shape[lead:]
        cols = shape[-1] if len(shape) >= 2 else 1
        return jnp.reshape(leaf, leaf.shape[:lead] + (-1, cols))

    def _k(self, size: int, fraction: float) -> int:
        return max(1, int(fraction * size))

    def _leaf_key(self, key, leaf_idx: int) -> jax.Array:
        return jax.random.fold_in(key, leaf_idx)

    # -- aggregation ----------------------------------------------------------

    def _slot_idx(self, slot):
        """Rule index for the round's shared slot (None when slot-free)."""
        if not self.rule.slotted or slot is None:
            return None
        return (slot,)

    def _beta(self, alpha: float) -> float | None:
        """Mean-table stepsize for the client-granular level (None = alpha)."""
        if self.mean_scale == 1.0:
            return None
        return self.mean_scale * alpha

    def aggregate(self, grads, state: DianaState | None, key, *, slot=None,
                  weight=None):
        """(direction, new_state); call inside shard_map over the wire axes.

        Composed two-level exchange: the inner (intra-pod) level over
        `client_axes` with `key`, then the outer (inter-pod) level over
        `pod_axes` with an independently salted key. Either level degrades
        to a passthrough when its axes are empty (flat wire / 1-client pod).

        `slot` is the round's shared batch index (scalar int32), consumed
        by per-slot methods ('diana_rr') to pick the shift-table row at
        both levels; other methods ignore it.

        `weight` is this rank's participation weight (scalar, pre-normalized
        by the host so an all-ones cohort gives exactly 1.0): the compressed
        message is scaled by it before the collective mean, which is how the
        buffered-async driver masks dropped/padded clients (weight 0) and
        discounts stale reports. It applies at the client-granular level
        (inner when `client_axes` is set, outer otherwise); None leaves the
        wire untouched.
        """
        if self.method == "dense":
            axes = tuple(self.client_axes) + tuple(self.pod_axes)
            g_in = grads if weight is None else jax.tree.map(
                lambda g: g * weight, grads)
            direction = jax.tree.map(lambda g: lax.pmean(g, axes), g_in)
            return direction, state
        cw = weight if self.client_axes else None
        pw = None if self.client_axes else weight
        direction, state = self.aggregate_local(grads, state, key, slot=slot,
                                                weight=cw)
        return self.aggregate_pod(direction, state, key, slot=slot, weight=pw)

    def aggregate_local(self, grads, state: DianaState | None, key, *,
                        slot=None, weight=None):
        """Inner level only: compressed exchange over `client_axes`.

        This is what each NASTYA local step runs — the pod's ranks psum
        their compressed gradients over the fast intra-pod ICI; the slow
        inter-pod wire is only touched once per epoch by `aggregate_pod`.
        """
        if self.method == "dense":
            g_in = grads if weight is None else jax.tree.map(
                lambda g: g * weight, grads)
            direction = jax.tree.map(
                lambda g: lax.pmean(g, self.client_axes), g_in
            )
            return direction, state
        if not self.client_axes:  # a pod of one client: no intra-pod wire
            return grads, state
        rule = self.rule
        h = state.shifts if rule.has_shifts else None
        mh = state.mean_shift if rule.has_mean else None
        dirs, new_h, new_mh = self._level(
            grads, h, mh, key,
            axes=self.client_axes,
            fold_axes=tuple(self.pod_axes) + tuple(self.client_axes),
            fraction=self.fraction, alpha=self.shift_lr,
            beta=self._beta(self.shift_lr),
            idx=self._slot_idx(slot), weight=weight,
        )
        if rule.has_shifts:
            state = state._replace(shifts=new_h, mean_shift=new_mh)
        return dirs, state

    def aggregate_pod(self, direction, state: DianaState | None, key, *,
                      slot=None, weight=None):
        """Outer level only: compressed exchange over `pod_axes`.

        `key` is the same round key given to `aggregate_local`; the actual
        coordinate draw uses fold_in(key, POD_KEY_SALT) so the two levels
        are independent. A single pod (`pod_size == 1`) has no inter-pod
        link: the exchange is the exact mean over the (size-1) pod axes —
        numerically the identity, which is what makes the 1-pod two-level
        wire bit-match the flat wire.

        With a per-slot method and `slot=None` (the NASTYA epoch gradient,
        which has no batch index) the rule falls back to table row 0.
        """
        if weight is not None and (not self.pod_axes or self.method == "dense"
                                   or self.pod_size == 1):
            direction = jax.tree.map(lambda g: g * weight, direction)
        if not self.pod_axes or self.method == "dense":
            if self.pod_axes:
                direction = jax.tree.map(
                    lambda g: lax.pmean(g, self.pod_axes), direction
                )
            return direction, state
        if self.pod_size == 1:
            direction = jax.tree.map(
                lambda g: lax.pmean(g, self.pod_axes), direction
            )
            return direction, state
        rule = self.rule
        pod_key = jax.random.fold_in(key, POD_KEY_SALT)
        h = state.pod_shifts if rule.has_shifts else None
        mh = state.pod_mean_shift if rule.has_mean else None
        # weight is only ever non-None here when this outer level IS the
        # client-granular level (client_axes=(), flat NASTYA fleets), and
        # then the pod tables are per-client too — so mean_scale applies.
        dirs, new_h, new_mh = self._level(
            direction, h, mh, pod_key,
            axes=self.pod_axes, fold_axes=tuple(self.pod_axes),
            fraction=self._pod_fraction, alpha=self.pod_shift_lr,
            beta=(self._beta(self.pod_shift_lr) if not self.client_axes
                  else None),
            idx=self._slot_idx(slot), weight=weight,
        )
        if rule.has_shifts:
            state = state._replace(pod_shifts=new_h, pod_mean_shift=new_mh)
        return dirs, state

    # -- one exchange level ----------------------------------------------------

    def _level(self, grads, h_tree, mh_tree, key, *, axes, fold_axes,
               fraction, alpha, beta=None, idx=None, weight=None):
        """One compressed exchange over `axes`: Q per rank, psum, rule update.

        Returns (direction_tree, new_shifts_tree, new_mean_shift_tree); the
        shift trees are None when h_tree is None. This module only owns the
        wire mechanics — select/payload/update/scatter all come from the
        shift rule (repro.core.rules), the same arithmetic the simulator
        drivers run.

        `beta` (None = alpha) is the mean-table stepsize handed to the rule;
        `weight` scales this rank's message into the collective mean (own
        message stays unweighted so the local shift update is unchanged).

        Two paths, chosen by the method and the wire alone:

        window  the shared wire with a `sparse_update` rule ('diana',
                'diana_rr'): `_window_leaf` reads and writes only the
                Rand-block window of g, h and H — no dense message leaf is
                formed, and the tables are written in place.
        dense   every other method or wire ('q', 'ef', the independent
                wire): the payload is formed over the whole leaf, the
                exchange returns dense Q(x) reconstructions and the rule
                updates whole leaves (the fused diana_shift kernel on
                'diana' over the independent wire).
        """
        rule = self.rule
        if h_tree is not None and self._window_path:
            return self._level_window(grads, h_tree, mh_tree, key, axes=axes,
                                      fraction=fraction, alpha=alpha,
                                      beta=beta, idx=idx, weight=weight)
        compress = (self._exchange_shared if self.wire == "shared"
                    else self._exchange_independent)
        leaves, treedef = jax.tree.flatten(grads)
        if h_tree is None:  # memory-free ('q'): direction = mean_m Q(g_m)
            out = []
            for i, g in enumerate(leaves):
                _, q_mean = compress(self._leaf_key(key, i), g, axes,
                                     fold_axes, fraction, weight=weight)
                out.append(q_mean.astype(g.dtype))
            return jax.tree.unflatten(treedef, out), None, None

        be = get_backend(self.backend)
        h_leaves = jax.tree.leaves(h_tree)
        mh_leaves = (jax.tree.leaves(mh_tree) if mh_tree is not None
                     else [None] * len(leaves))
        dirs, new_h, new_mh = [], [], []
        for i, (g, ht, mht) in enumerate(zip(leaves, h_leaves, mh_leaves)):
            h = rule.select(ht, idx)  # shift_dtype table row (or residual)
            mh = rule.select(mht, idx) if mht is not None else None
            p = rule.payload(g.astype(jnp.float32), h.astype(jnp.float32))
            q_own, q_mean = compress(self._leaf_key(key, i), p, axes,
                                     fold_axes, fraction,
                                     contractive=rule.contractive,
                                     weight=weight)
            direction, h_new, mh_new = rule.update(
                h, q_own.astype(jnp.float32), mh, q_mean.astype(jnp.float32),
                alpha=alpha, beta=beta, backend=be, payload=p,
            )
            new_h.append(rule.scatter(ht, idx, h_new.astype(ht.dtype)))
            if mht is not None:
                new_mh.append(rule.scatter(mht, idx, mh_new.astype(mht.dtype)))
            dirs.append(direction.astype(g.dtype))
        return (jax.tree.unflatten(treedef, dirs),
                jax.tree.unflatten(treedef, new_h),
                jax.tree.unflatten(treedef, new_mh) if mh_tree is not None
                else None)

    @property
    def _window_path(self) -> bool:
        """Whether the compressed levels take the window-sparse path."""
        return self.wire == "shared" and self.rule.sparse_update

    def _level_window(self, grads, h_tree, mh_tree, key, *, axes, fraction,
                      alpha, beta=None, idx=None, weight=None):
        """`_level` on the window path; same returns, same arithmetic.

        Per leaf, only the circular Rand-block window — the kb*8 rows the
        shared draw selects — is read from g, h and H, and only it is
        written back. Outside the window the message is zero, so the rule
        leaves h and H as they are and the direction is H itself: the one
        dense pass left is H cast to the gradient's dtype.
        """
        rule = self.rule
        slot = None
        if rule.slotted:  # PerSlotShift.select's row 0 on slot-free rounds
            slot = jnp.int32(0) if idx is None else idx[0]
        leaves, treedef = jax.tree.flatten(grads)
        dirs, new_h, new_mh = [], [], []
        for i, (g, ht, mht) in enumerate(zip(leaves, jax.tree.leaves(h_tree),
                                             jax.tree.leaves(mh_tree))):
            with jax.named_scope("window"):
                d, h, mh = self._window_leaf(
                    g, ht, mht, self._leaf_key(key, i), axes=axes,
                    fraction=fraction, alpha=alpha, beta=beta, slot=slot,
                    weight=weight)
            dirs.append(d)
            new_h.append(h)
            new_mh.append(mh)
        return (jax.tree.unflatten(treedef, dirs),
                jax.tree.unflatten(treedef, new_h),
                jax.tree.unflatten(treedef, new_mh))

    def _window_leaf(self, g, ht, mht, key, *, axes, fraction, alpha, beta,
                     slot, weight):
        """One leaf of `_level_window` -> (direction, h table, H table).

        The draw, the slab and its exchange are those of `_exchange_shared`
        (same key, start_block, nb/kb scale after the subtraction, transport
        and quantization uniforms); the update is the rule's own, in plain
        jnp (the reference backend) so XLA fuses it with the gathers.
        """
        rule = self.rule
        be = get_backend(self.backend)
        lead = 0 if slot is None else 1
        g_rows = self._row_view(g)
        n = g_rows.shape[0]
        nb, kb = self._wire_geometry(n + (-n) % BLOCK_ROWS, fraction)
        start_block = jax.random.randint(key, (), 0, nb)
        # the window's rows, circular over the block-padded leaf; a padding
        # row (index >= n) is out of range and reads as zero
        rows = (start_block * BLOCK_ROWS + jnp.arange(kb * BLOCK_ROWS)) % (
            nb * BLOCK_ROWS)
        lead_at = () if slot is None else (slot,)

        def window(x, at=()):
            return x.at[at + (rows,)].get(mode="fill", fill_value=0)

        h_w = window(self._row_view(ht, lead), lead_at)
        mh_w = window(self._row_view(mht, lead), lead_at)
        p_w = rule.payload(window(g_rows).astype(jnp.float32),
                           h_w.astype(jnp.float32))
        vals = p_w * (nb / kb)
        levels = self._quant_levels
        quant_u = None
        if levels is not None:
            qkey = jax.random.fold_in(key, WIRE_QUANT_SALT)
            quant_u = jax.random.uniform(qkey, vals.shape)
        own, mean = be.exchange_slab(vals, axes=axes, weight=weight,
                                     wire_dtype=self.wire_dtype,
                                     levels=levels, quant_u=quant_u)
        dir_w, h_w, mh_w = rule.update(h_w, own, mh_w, mean, alpha=alpha,
                                       beta=beta,
                                       backend=get_backend("reference"))
        write = partial(self._write_window, start_block=start_block, nb=nb)
        new_ht = write(ht, h_w, slot=slot)
        new_mht = write(mht, mh_w, slot=slot)
        h_mean = new_mht if slot is None else new_mht[slot]
        direction = write(h_mean.astype(g.dtype), dir_w, slot=None)
        return direction, new_ht, new_mht

    def _write_window(self, x, vals, *, start_block, nb, slot):
        """`x` (a leaf, or with `slot` a slot table) with the window's rows
        set to `vals`, in place through the backend's write-back kernel.
        Rows are padded to a block multiple only where they are not one."""
        be = get_backend(self.backend)
        lead = 0 if slot is None else 1
        rows = self._row_view(x, lead)
        n, cols = rows.shape[-2:]
        pad = (-n) % BLOCK_ROWS
        if pad:
            rows = jnp.pad(rows, [(0, 0)] * lead + [(0, pad), (0, 0)])
        base = jnp.int32(0) if slot is None else slot * nb
        flat = be.wire_decompress_into(
            jnp.reshape(rows, (-1, cols)), vals.astype(x.dtype), start_block,
            base, n_rows=n + pad, block_rows=BLOCK_ROWS)
        rows = jnp.reshape(flat, rows.shape)
        if pad:
            rows = rows[..., :n, :]
        return jnp.reshape(rows, x.shape)

    # shared-seed Rand-block: sparse collectives -------------------------------
    #
    # The circular window is block-granular (whole BLOCK_ROWS=8 row groups)
    # so the gather/scatter maps onto the Pallas kernels' sublane-aligned
    # VMEM copies. Rows are zero-padded up to a block multiple; padding rows
    # travel (zeros) but never reach real coordinates on reconstruction.
    # Marginal inclusion probability is k_blocks/n_blocks for every real row
    # -> unbiased with the same omega formula (DESIGN.md §3.2).

    def _pad_rows(self, rows):
        pad = (-rows.shape[0]) % BLOCK_ROWS
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        return rows

    def _wire_geometry(self, n_rows_padded: int,
                       fraction: float) -> tuple[int, int]:
        nb = n_rows_padded // BLOCK_ROWS
        return nb, max(1, int(fraction * nb))

    def _exchange_shared(self, key, delta, axes, fold_axes, fraction,
                         contractive: bool = False, weight=None):
        """Shared-key Rand-block exchange of one leaf over `axes`.

        Returns (q_own, q_mean) dense reconstructions. Only the k-row slab
        crosses the wire (the sparse collective runs inside the backend's
        `wire_exchange`); both reconstructions reuse the one start_block.

        contractive=True divides out the unbiased nb/kb scaling — the
        UNSCALED window projection (contraction factor kb/nb) that error
        feedback requires; the d/k-scaled reconstruction makes the EF
        residual grow instead of contract. `weight` scales this rank's slab
        into the collective mean only (q_own stays unweighted).

        Transport is `wire_dtype`/`wire_levels` (DESIGN.md §3.13): when the
        slab is quantized, the stochastic-rounding uniforms come from the
        level key + WIRE_QUANT_SALT — shared across the level's ranks like
        the window draw, so every rank agrees on the byte lattice.
        """
        del fold_axes  # shared draw: every rank uses the same key
        be = get_backend(self.backend)
        rows = self._pad_rows(self._row_view(delta))
        nb, kb = self._wire_geometry(rows.shape[0], fraction)
        start_block = jax.random.randint(key, (), 0, nb)
        levels = self._quant_levels
        quant_u = None
        if levels is not None:
            qkey = jax.random.fold_in(key, WIRE_QUANT_SALT)
            quant_u = jax.random.uniform(
                qkey, (kb * BLOCK_ROWS, rows.shape[1]))
        vals, mean_vals = be.wire_exchange(rows, start_block, k_blocks=kb,
                                           block_rows=BLOCK_ROWS, axes=axes,
                                           weight=weight,
                                           wire_dtype=self.wire_dtype,
                                           levels=levels, quant_u=quant_u)
        if contractive:
            vals = vals * (kb / nb)
            mean_vals = mean_vals * (kb / nb)
        return (self._scatter_block(delta, start_block, vals),
                self._scatter_block(delta, start_block, mean_vals))

    def _scatter_block(self, template, start_block, vals):
        be = get_backend(self.backend)
        shape = self._row_view(template).shape
        n_padded = shape[0] + (-shape[0]) % BLOCK_ROWS
        dense = be.wire_decompress(vals, start_block, n_rows=n_padded,
                                   block_rows=BLOCK_ROWS)
        return jnp.reshape(dense[:shape[0]], template.shape)

    # independent-seed Rand-k: paper-exact, dense collectives ------------------

    def _exchange_independent(self, key, delta, axes, fold_axes, fraction,
                              contractive: bool = False, weight=None):
        """Unbiased Rand-k over rows (with-replacement indices: omega <= n/k,
        avoids a full permutation sort on device; see DESIGN.md §3), one
        independent draw per rank (key folded with the rank's coordinates
        along `fold_axes`), then a dense psum over `axes`.
        contractive=True keeps the selected rows UNSCALED (set semantics:
        duplicate draws count once) — the projection error feedback needs.
        `weight` scales this rank's contribution to the mean only."""
        for ax in fold_axes:
            key = jax.random.fold_in(key, lax.axis_index(ax))
        rows = self._row_view(delta.astype(jnp.float32))
        n = rows.shape[0]
        k = self._k(n, fraction)
        idx = jax.random.randint(key, (k,), 0, n)
        if contractive:
            out = jnp.reshape(
                jnp.zeros_like(rows).at[idx].set(rows[idx]), delta.shape)
        else:
            vals = rows[idx] * (n / k)
            out = jnp.reshape(
                jnp.zeros_like(rows).at[idx].add(vals), delta.shape)
        shared = out if weight is None else out * weight
        return out, lax.pmean(shared, axes)

    # -- wire accounting (benchmarks / EXPERIMENTS.md) -------------------------

    def wire_bytes_per_round(self, params) -> dict[str, int]:
        """Bytes one rank contributes to each wire level per round.

        'intra_pod' is the inner shared-wire slab (k-row blocks);
        'inter_pod' the outer level's slab; 'dense' what an uncompressed
        psum of the same tree would move. The shared wire's sparse
        collective moves exactly the compressed slab — at the transport
        width of `wire_dtype` (`payload_itemsize`), plus the packed modes'
        f32 per-row scale sideband — while the independent wire moves the
        dense size regardless of k (the zeros travel — DESIGN.md §3.1).
        The jaxpr census (analysis/graph.py) pins the compiled step's
        collective payloads against these numbers exactly, and the fleet
        driver charges `FedState.bits` from them.
        """
        dense = intra = inter = 0
        for leaf in jax.tree.leaves(params):
            rows = int(np.prod(leaf.shape[:-1])) if leaf.ndim >= 2 else int(
                np.prod(leaf.shape))
            cols = leaf.shape[-1] if leaf.ndim >= 2 else 1
            padded = rows + (-rows) % BLOCK_ROWS
            dense += rows * cols * jnp.dtype(leaf.dtype).itemsize
            if self.method == "dense" or self.wire == "independent":
                continue
            item = payload_itemsize(self.wire_dtype, self.rule, leaf.dtype)

            def slab_bytes(fraction):
                _, kb = self._wire_geometry(padded, fraction)
                slab_rows = kb * BLOCK_ROWS
                return int(slab_rows * cols * item) + scale_sideband_bytes(
                    self.wire_dtype, slab_rows)

            if self.client_axes:
                intra += slab_bytes(self.fraction)
            if self.pod_axes and self.pod_size > 1:
                inter += slab_bytes(self._pod_fraction)
        if self.method != "dense" and self.wire == "independent":
            intra = dense if self.client_axes else 0
            inter = dense if (self.pod_axes and self.pod_size > 1) else 0
        return {"dense": dense, "intra_pod": intra, "inter_pod": inter}

    def wire_paths(self, params) -> dict[str, dict[str, int]]:
        """Leaves, and their elements, that each wire level runs on the
        window path and on the dense path of `_level` (its docstring).

        One entry per level that exists, keyed like `wire_bytes_per_round`:
        'intra_pod' when `client_axes` is set, 'inter_pod' when there is an
        inter-pod link. Each holds `window_leaves`, `window_elements`,
        `dense_leaves` and `dense_elements`.
        """
        leaves = jax.tree.leaves(params)
        counts = {"leaves": len(leaves),
                  "elements": sum(int(np.prod(x.shape)) for x in leaves)}
        window = self.method != "dense" and self._window_path
        levels = []
        if self.client_axes:
            levels.append("intra_pod")
        if self.pod_axes and self.pod_size > 1:
            levels.append("inter_pod")
        return {level: {f"{path}_{k}": v if (path == "window") == window
                        else 0
                        for path in ("window", "dense")
                        for k, v in counts.items()}
                for level in levels}
