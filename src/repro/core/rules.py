"""The shift-rule layer: one source of truth for shift/control-variate
arithmetic across BOTH execution paths (DESIGN.md §3.8).

The paper's design space varies exactly one thing between methods: what a
client remembers between rounds and how that memory shapes what crosses the
wire. Four rules cover every method in the repo:

``NoShift``      no memory: send Q(g)                 (SGD/QSGD/RR/Q-RR, 'q')
``SingleShift``  one DIANA control variate h per client: send Q(g - h),
                 h += alpha*Q  (DIANA, DIANA-NASTYA, wire method 'diana')
``PerSlotShift`` a table of n control variates per client, the round's batch
                 index selects the slot (DIANA-RR Algorithm 3, wire method
                 'diana_rr')
``EfRule``       error feedback (Stich et al. 2018): memory is the
                 compression residual e; send C(gamma*g + e), keep what the
                 compressor dropped ('ef_topk_rr', wire method 'ef')

Both consumers dispatch through the same instances:

- the simulator drivers (`core.algorithms._nonlocal_epoch`/`_local_epoch`)
  call the rules on whole client-stacked pytrees (leaves `(M, ...)`, the
  per-slot index is `(arange(M), col)`);
- the production wire (`core.dist.CompressedAggregation._level`) calls them
  per leaf inside the fully-manual shard_map region (the client axis is the
  mesh, the per-slot index is the round's shared scalar slot).

That polymorphism is free because every rule method is either a
`jax.tree.map` (works on bare arrays — an array is a pytree) or dispatches
to the compression backend, which has tree (`tree_diana_shift`, one fused
kernel launch over the raveled buffer) and flat (`diana_shift_flat`) entry
points for the same fused DIANA update. On the shared wire a
`sparse_update` rule's `update` gets only the Rand-block window's slabs and
the reference backend (plain jnp, fused by XLA with the window gathers).

Slot semantics on the wire: every rank of a wire level must use the SAME
slot in a given round (the mean-shift table update `mh[s] += alpha*q_mean`
is only locally computable when all ranks touch the same row s; per-rank
slots would need a dense collective of `h_m[slot_m]`, forfeiting the sparse
wire). The data side provides this via `ReshuffleSampler(mode="rr_shared")`
— one permutation per epoch shared by every client — and
`data.pipeline.shared_slots_for_step`. The simulator keeps the paper-exact
independent per-client permutations (everything is on one device there).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Index = Any  # tuple of index arrays applied as table[idx], or None


def _lead_zeros(params, lead: tuple[int, ...], dtype):
    return jax.tree.map(
        lambda p: jnp.zeros(lead + p.shape, dtype or p.dtype), params)


@dataclasses.dataclass(frozen=True)
class ShiftRule:
    """Protocol + shared plumbing for the four rules.

    Capability flags drive state allocation in both consumers:

    has_shifts      the rule keeps per-client/rank memory
    has_mean        the rule keeps a running mean table (the wire's
                    `mean_shift`; the local family's `server_h`)
    needs_server_h  the simulator allocates `FedState.server_h`
    slotted         memory tables carry a leading slot axis
    supports_local  legal in the local (NASTYA) simulator family
    contractive     the wire must apply the UNSCALED (contractive)
                    compression to this rule's payload (EF diverges under
                    the unbiased d/k-scaled reconstruction)
    sparse_update   where the compressed message is zero, memory is
                    unchanged and the direction equals the mean table: the
                    update is an axpy on the message. The shared wire then
                    reads and writes only the Rand-block window of the
                    gradient and the tables (`core.dist`, DESIGN.md §3.5)
    """

    name: str = "none"
    has_shifts: bool = False
    has_mean: bool = False
    needs_server_h: bool = False
    slotted: bool = False
    supports_local: bool = True
    contractive: bool = False
    sparse_update: bool = False

    # -- state layout ---------------------------------------------------------

    def init_shifts(self, params, m: int | None = None, *, n_slots: int = 1,
                    dtype=None):
        """Zero memory tables shaped for this rule.

        m=None gives the wire layout (per-rank local blocks, no client
        axis); an integer m prepends the stacked client axis (simulator /
        TrainState layouts). Slotted rules insert the `n_slots` axis next.
        """
        del n_slots, dtype  # analysis: allow[ignored-argument] stateless rule keeps no tables
        del params, m  # analysis: allow[ignored-argument] stateless rule keeps no tables
        return None

    # -- per-round arithmetic -------------------------------------------------

    def select(self, shifts, idx: Index):
        """The active memory view for this round (slot tables index here)."""
        del idx  # analysis: allow[ignored-argument] unslotted tables have one view
        return shifts

    def payload(self, g, h, *, gamma: float = 1.0):
        """What goes through the compressor."""
        del h, gamma  # analysis: allow[ignored-argument] shift-free payload is the raw gradient
        return g

    def update(self, h, q_own, mh, q_mean, *, alpha: float,
               beta: float | None = None, gamma: float = 1.0, backend,
               payload=None):
        """Post-compression arithmetic: (direction, h_new, mh_new).

        h/q/mh are matching pytrees (the simulator passes whole stacked
        trees; the wire passes single leaves). `q_own` is this client's
        compressed message, `q_mean` the aggregated one; the simulator's
        per-client view passes the same tree for both. `beta` is the
        mean-table stepsize (defaults to alpha); cohort-sampled fleets use
        beta = (M/C)*alpha so the resident mean tracks the population mean.
        """
        del h, q_own, mh, alpha, beta, gamma, backend, payload  # analysis: allow[ignored-argument] memory-free rule: direction is the aggregate itself
        return q_mean, None, None

    def scatter(self, shifts, idx: Index, h_new):
        """Write the round's updated memory back into the table."""
        del idx, h_new  # analysis: allow[ignored-argument] no tables to write back
        return shifts

    # -- local (NASTYA) family server side ------------------------------------

    def direction(self, server_h, q_mean, *, alpha: float, gamma: float = 1.0,
                  backend):
        """(direction, new_server_h) from the aggregated epoch message."""
        del alpha, gamma, backend  # analysis: allow[ignored-argument] shift-free server applies the aggregate directly
        return q_mean, server_h

    def table_axpy(self, shifts, q, *, alpha: float):
        """Local-family client-table update h += alpha*q (the fused kernel
        would write discarded M-times-param-sized outputs here)."""
        del q, alpha  # analysis: allow[ignored-argument] no client tables to update
        return shifts


@dataclasses.dataclass(frozen=True)
class NoShift(ShiftRule):
    name: str = "none"


@dataclasses.dataclass(frozen=True)
class SingleShift(ShiftRule):
    """DIANA: one control variate per client, one mean per server/level."""

    name: str = "single"
    has_shifts: bool = True
    has_mean: bool = True
    needs_server_h: bool = True
    sparse_update: bool = True

    def init_shifts(self, params, m=None, *, n_slots=1, dtype=None):
        del n_slots  # analysis: allow[ignored-argument] unslotted: one shift per client
        return _lead_zeros(params, () if m is None else (m,), dtype)

    def payload(self, g, h, *, gamma: float = 1.0):
        del gamma  # analysis: allow[ignored-argument] DIANA payload g-h is stepsize-free
        return jax.tree.map(jnp.subtract, g, h)

    def update(self, h, q_own, mh, q_mean, *, alpha, beta=None, gamma=1.0,
               backend, payload=None):
        del gamma, payload  # analysis: allow[ignored-argument] fused DIANA update needs only alpha/beta
        # the fused path: direction = H + Q_mean, h' = h + alpha*Q_own,
        # H' = H + beta*Q_mean in ONE pass (kernels/diana_shift.py)
        if isinstance(h, jax.Array):
            return backend.diana_shift_flat(h, q_own, mh, q_mean, alpha=alpha,
                                            beta=beta)
        return backend.tree_diana_shift(h, q_own, mh, q_mean, alpha=alpha,
                                        beta=beta)

    def scatter(self, shifts, idx, h_new):
        del shifts, idx  # analysis: allow[ignored-argument] unslotted table IS the round's view
        return h_new

    def direction(self, server_h, q_mean, *, alpha, gamma=1.0, backend):
        d, _, new_h = self.update(server_h, q_mean, server_h, q_mean,
                                  alpha=alpha, gamma=gamma, backend=backend)
        return d, new_h

    def table_axpy(self, shifts, q, *, alpha):
        return jax.tree.map(lambda h, qi: h + alpha * qi, shifts, q)


@dataclasses.dataclass(frozen=True)
class PerSlotShift(SingleShift):
    """DIANA-RR (Algorithm 3): n control variates per client; the batch
    index selects which one a round reads and writes. Same fused update as
    SingleShift — only the table layout and the select/scatter differ."""

    name: str = "per_slot"
    slotted: bool = True
    needs_server_h: bool = False
    supports_local: bool = False

    def init_shifts(self, params, m=None, *, n_slots=1, dtype=None):
        lead = (() if m is None else (m,)) + (n_slots,)
        return _lead_zeros(params, lead, dtype)

    def select(self, shifts, idx):
        if idx is None:
            idx = (0,)  # slot-less rounds (the NASTYA epoch gradient)
        return jax.tree.map(lambda s: s[idx], shifts)

    def scatter(self, shifts, idx, h_new):
        if idx is None:
            idx = (0,)
        return jax.tree.map(lambda s, hn: s.at[idx].set(hn), shifts, h_new)


@dataclasses.dataclass(frozen=True)
class EfRule(ShiftRule):
    """Error feedback: memory is the compression residual. Needs a
    CONTRACTIVE compressor (Top-k in the simulator; the wire applies the
    unscaled Rand-block window, contraction factor k/d).

    The simulator form is p = gamma*g + e, direction = C(p)/gamma (the
    common `params - gamma*direction` update divides gamma back out); the
    wire passes gamma=1 — identical trajectories for positively homogeneous
    compressors (C(cx) = c·C(x), true of Top-k/Rand-k/QSGD), since e then
    just carries a constant gamma factor.
    """

    name: str = "ef"
    has_shifts: bool = True
    supports_local: bool = False
    contractive: bool = True

    def init_shifts(self, params, m=None, *, n_slots=1, dtype=None):
        del n_slots  # analysis: allow[ignored-argument] EF keeps one residual per client
        return _lead_zeros(params, () if m is None else (m,), dtype)

    def payload(self, g, h, *, gamma: float = 1.0):
        return jax.tree.map(lambda gi, e: gamma * gi + e, g, h)

    def update(self, h, q_own, mh, q_mean, *, alpha, beta=None, gamma=1.0,
               backend, payload=None):
        del h, alpha, beta, backend  # analysis: allow[ignored-argument] EF memory is payload-q, no stepsize
        direction = q_mean if gamma == 1.0 else jax.tree.map(
            lambda q: q / gamma, q_mean)
        new_e = jax.tree.map(jnp.subtract, payload, q_own)
        return direction, new_e, mh

    def scatter(self, shifts, idx, h_new):
        del shifts, idx  # analysis: allow[ignored-argument] residual table IS the round's view
        return h_new


RULES: dict[str, ShiftRule] = {
    "none": NoShift(),
    "single": SingleShift(),
    "per_slot": PerSlotShift(),
    "ef": EfRule(),
}

# production wire method name -> rule ('dense' skips compression entirely
# but shares NoShift's no-memory semantics)
WIRE_RULES: dict[str, ShiftRule] = {
    "dense": RULES["none"],
    "q": RULES["none"],
    "diana": RULES["single"],
    "diana_rr": RULES["per_slot"],
    "ef": RULES["ef"],
}


def get_rule(name: str) -> ShiftRule:
    try:
        return RULES[name]
    except KeyError:
        raise ValueError(
            f"unknown shift rule {name!r}; options: {sorted(RULES)}")
