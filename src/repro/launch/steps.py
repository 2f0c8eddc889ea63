"""Production step functions: train (paper's compressed-RR wire) + serve.

`make_train_step` is where the paper's contribution meets the pod:

  - the mesh's ("pod","data") ranks are the M federated clients; per-client
    gradients are computed under GSPMD (`jax.vmap` over the stacked client
    batch, "model" tensor parallelism compiler-managed);
  - the WIRE — compression, shift updates, and the sparse collectives — runs
    in a fully-manual `jax.shard_map` over every mesh axis, so the paper's
    per-client semantics are explicit;
  - `CompressedAggregation` (core/dist.py) is hierarchical: the "data" axis
    inside a pod runs the kernelized shared Rand-block psum and the "pod"
    axis runs a second, independently-keyed compressed exchange with its own
    DIANA shifts (DESIGN.md §3.6);
  - with `local_steps > 1` the step is the paper's Q-NASTYA / DIANA-NASTYA
    (Algorithms 4-5) at pod granularity: each pod runs `local_steps` local
    RR mini-epochs at stepsize `lr` (gamma), the epoch gradient
    (x_t - x^n) / (gamma * n) crosses the inter-pod wire once, and the
    server update reuses `optim` at the server stepsize `eta`;
  - the server update is plain SGD (Algorithms 2-5; momentum/AdamW are the
    beyond-paper variants, state replicated over clients, TP over model).

The train step names its phases with `jax.named_scope`: `client_grads`
(forward and backward, with the recomputed forward under remat), `wire`
(every exchange: compression, collectives, kernels, shift updates) and
`server_update`. The scopes are metadata only; they reach the compiled
program's `op_name`s, so a profiler trace can be split by phase.

`make_prefill_step` / `make_serve_step` are pure-GSPMD inference paths (no
client wire — serving has no gradients to compress).

The step's `shifts` are NOT assumed to belong to mesh-resident clients:
under partial participation (`repro.fleet`, DESIGN.md §3.9) each round's
cohort slice is swapped in via `with_cohort_shifts` and scattered back to
the host `ClientStateStore` after the step — same compiled step, O(cohort)
device memory.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import salts
from repro.core.dist import CompressedAggregation, DianaState
from repro.launch import sharding
from repro.launch.mesh import (
    client_axes as _client_axes,
    data_axes as _data_axes,
    num_clients,
    num_pods,
    pod_axes as _pod_axes,
)
from repro.models import transformer
from repro.models.config import ArchConfig
from repro.optim import optimizers as optim


class TrainState(NamedTuple):
    """Production train state. Shift-table layouts follow the aggregation
    method's rule (repro.core.rules): 'diana' keeps one (M, *param) shift
    per client; 'diana_rr' inserts an n_slots axis after the client/pod
    axis on every table ((M, n_slots, *param) etc.); 'ef' keeps only the
    per-client residual in `shifts` (mean tables None)."""

    params: Any
    shifts: Any  # (M, [n_slots,] *param) intra-pod shift/residual, or None
    mean_shift: Any  # per-pod mean: (P, [ns,] *param) on pod meshes, else ([ns,] *param)
    step: jax.Array
    opt_state: Any = ()  # server optimizer state (paper uses plain SGD)
    pod_shifts: Any = None  # (P, [ns,] *param) inter-pod shifts, or None
    pod_mean_shift: Any = None  # ([ns,] *param) global mean of pod shifts, or None


def configure_agg(agg: CompressedAggregation, mesh,
                  local_steps: int = 1) -> CompressedAggregation:
    """Bind an aggregation config to a mesh's wire topology.

    - multi-pod mesh: inner level over the in-pod "data" ranks, outer level
      over "pod" (the two-level wire, DESIGN.md §3.6);
    - flat mesh with local steps: every client is its own pod (paper
      Algorithms 4-5 exactly — no intra-pod wire, one compressed exchange
      per epoch over the client axes);
    - flat mesh, no local steps: the single-level wire, unchanged.
    """
    # on NASTYA paths the inter-pod wire only carries the slot-free epoch
    # gradient (row 0), so outer slot tables collapse to one row
    pod_slots = 1 if local_steps > 1 else agg.pod_slots
    if _pod_axes(mesh):
        return dataclasses.replace(
            agg, client_axes=_data_axes(mesh), pod_axes=_pod_axes(mesh),
            pod_size=num_pods(mesh), pod_slots=pod_slots)
    if local_steps > 1:
        return dataclasses.replace(
            agg, client_axes=(), pod_axes=_client_axes(mesh),
            pod_size=num_clients(mesh), pod_slots=pod_slots)
    return dataclasses.replace(agg, client_axes=_client_axes(mesh),
                               pod_axes=(), pod_size=1)


def _outer_ranks(agg: CompressedAggregation) -> int:
    """Number of outer-level ranks ("pods"): pod_size when hierarchical."""
    return agg.pod_size if agg.pod_axes else 1


# ---------------------------------------------------------------------------
# state construction (concrete + abstract for the dry-run)
# ---------------------------------------------------------------------------

def _make_optimizer(optimizer: str, lr: float) -> optim.Optimizer:
    if optimizer == "sgd":
        return optim.sgd(lr)
    if optimizer == "momentum":
        return optim.momentum(lr)
    if optimizer == "adamw":
        return optim.adamw(lr, weight_decay=0.1)
    raise ValueError(optimizer)


def init_train_state(key, cfg: ArchConfig, agg: CompressedAggregation,
                     m: int, *, optimizer: str = "sgd", lr: float = 3e-3,
                     mesh=None, local_steps: int = 1) -> TrainState:
    """Initial state. Pass `mesh` (and `local_steps`) so the DIANA shift
    tables get the mesh's wire topology; without it `agg` is used as-is
    (correct for flat single-level meshes, the pre-pod behaviour)."""
    if mesh is not None:
        agg = configure_agg(agg, mesh, local_steps)
    params = transformer.init_params(key, cfg)
    shifts = mean_shift = pod_shifts = pod_mean_shift = None
    rule = agg.rule
    if rule.has_shifts:
        init = lambda lead, ns: rule.init_shifts(
            params, lead, n_slots=ns, dtype=agg.shift_dtype)
        n_pods_ = _outer_ranks(agg)
        if agg.client_axes:
            shifts = init(m, agg.n_slots)
            if rule.has_mean:
                mean_shift = init(n_pods_ if agg.pod_axes else None,
                                  agg.n_slots)
        if agg.pod_axes:
            pod_shifts = init(n_pods_, agg._pod_slots)
            if rule.has_mean:
                pod_mean_shift = init(None, agg._pod_slots)
    opt_state = _make_optimizer(optimizer, lr).init(params)
    return TrainState(params, shifts, mean_shift, jnp.zeros((), jnp.int32),
                      opt_state, pod_shifts, pod_mean_shift)


def abstract_train_state(cfg: ArchConfig, agg: CompressedAggregation,
                         m: int, *, optimizer: str = "sgd", mesh=None,
                         local_steps: int = 1) -> TrainState:
    return jax.eval_shape(
        lambda: init_train_state(salts.root_key(0, salts.PARAMS_KEY_SALT),
                                 cfg, agg, m, optimizer=optimizer, mesh=mesh,
                                 local_steps=local_steps)
    )


def train_state_shardings(mesh, state: TrainState, agg) -> TrainState:
    caxes = _client_axes(mesh)
    paxes = _pod_axes(mesh) or (agg.pod_axes if agg.pod_axes else ())
    ns = lambda spec: NamedSharding(mesh, spec)
    pspecs = sharding.param_specs(state.params, mesh=mesh)
    # slot-axis presence is keyed on the RULE (size-1 tables still carry the
    # axis); 0 means no axis. Outer-level tables may have fewer rows
    # (configure_agg collapses them to 1 on NASTYA paths).
    nslots = agg.n_slots if agg.rule.slotted else 0
    pod_nslots = agg._pod_slots if agg.rule.slotted else 0

    def maybe(tree, spec_tree):
        return None if tree is None else jax.tree.map(ns, spec_tree)

    # mean_shift is per-pod (leading pod axis) on hierarchical wires
    podded = (sharding.podded_specs(state.params, paxes, mesh=mesh,
                                    n_slots=nslots)
              if paxes else None)
    podded_pod = (sharding.podded_specs(state.params, paxes, mesh=mesh,
                                        n_slots=pod_nslots)
                  if paxes else None)
    slotted = sharding.slotted_specs(state.params, mesh=mesh, n_slots=nslots)
    ms_specs = podded if (state.mean_shift is not None and agg.pod_axes) \
        else slotted

    # optimizer state: mu/nu shard like params, scalars replicated
    if state.opt_state == ():
        osh = ()
    elif isinstance(state.opt_state, optim.AdamState):
        osh = optim.AdamState(
            mu=jax.tree.map(ns, pspecs), nu=jax.tree.map(ns, pspecs),
            count=ns(P()))
    elif (jax.tree.structure(state.opt_state)
          == jax.tree.structure(state.params)):
        osh = jax.tree.map(ns, pspecs)  # momentum: param-shaped
    else:
        osh = jax.tree.map(lambda _: ns(P()), state.opt_state)
    return TrainState(
        params=jax.tree.map(ns, pspecs),
        shifts=maybe(state.shifts,
                     sharding.shifts_specs(state.params, caxes, mesh=mesh,
                                           n_slots=nslots)),
        mean_shift=maybe(state.mean_shift, ms_specs),
        step=ns(P()),
        opt_state=osh,
        pod_shifts=maybe(state.pod_shifts, podded_pod),
        pod_mean_shift=maybe(state.pod_mean_shift,
                             sharding.slotted_specs(state.params, mesh=mesh,
                                                    n_slots=pod_nslots)),
    )


def with_cohort_shifts(state: TrainState, host_shifts, shardings: TrainState,
                       field: str = "shifts") -> TrainState:
    """Swap cohort-gathered shift slices into a TrainState (fleet path).

    The train step never assumes `shifts` belongs to mesh-resident clients —
    it runs the rule arithmetic on whatever (M, [n_slots,] *param) slice it
    is handed. Under partial participation (`repro.fleet.FleetRunner`) that
    slice is the round's cohort, gathered from the host
    `ClientStateStore` and placed onto the step's shift shardings here;
    after the step the runner scatters the field back. `host_shifts`
    is None for memory-free methods ('q'/'dense') — the state passes
    through untouched. Device memory stays O(cohort), never O(population).

    `field` selects which table holds the per-client state: "shifts" when
    the mesh's client ranks are the inner wire level, "pod_shifts" on flat
    NASTYA meshes (`configure_agg` with `client_axes=()` maps each client to
    its own pod, so the per-client DIANA state lives in the outer tables).
    """
    if host_shifts is None:
        return state
    return state._replace(
        **{field: jax.device_put(host_shifts, getattr(shardings, field))})


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, mesh, *, agg: CompressedAggregation,
                    lr: float = 3e-3, eta: float | None = None,
                    local_steps: int = 1, remat="full", unroll: bool = False,
                    ce: str = "gather", seq_shard: bool = True,
                    optimizer: str = "sgd", elastic: bool = False,
                    debug_metrics: bool = False):
    """Returns jitted (state, batch, key) -> (state, metrics).

    lr: the client/local stepsize gamma. With `local_steps == 1` it is also
    the server stepsize (Algorithms 2-3). With `local_steps > 1` the step is
    NASTYA at pod granularity (Algorithms 4-5): `eta` is the server stepsize
    applied to the epoch gradient (default gamma * local_steps, which makes
    Q-NASTYA degrade to FedRR per the Corollary 3 remark); the batch must
    carry `local_steps` micro-batches per client, client-major
    (leading dim = M * local_steps * b).

    Per-slot methods (`agg.method == "diana_rr"`) change the signature to
    (state, batch, key, slots): `slots` is a (local_steps,) int32 vector of
    the SHARED batch indices this step's micro-batches occupy in every
    client's dataset — `data.pipeline.shared_slots_for_step` derives it
    from the `rr_shared` sampler that also orders the batch stream. With
    local_steps == 1 the single slot drives the round's shift-table row at
    both wire levels; in NASTYA mode the slots ride the per-pod micro-epoch
    permutation and index the intra-pod tables, while the inter-pod
    exchange of the (slot-free) epoch gradient uses table row 0.

    optimizer: the SERVER update applied to the aggregated direction —
    "sgd" is the paper's Algorithms 2-5; "momentum"/"adamw" are the
    beyond-paper variants (state replicated over clients, TP over model).

    elastic: the step takes a trailing (m,) f32 `weights` vector — each
    client rank's participation weight, pre-normalized by the host so an
    all-ones cohort is exactly 1.0 everywhere (x * 1.0 is a bitwise no-op,
    so full participation matches the non-elastic step bit-for-bit). The
    async fleet driver (repro.fleet, DESIGN.md §3.10) uses weight 0 to mask
    dropped/padded clients and fractional weights to discount stale
    reports; the cohort can shrink/grow between rounds without recompiling.

    debug_metrics: opt-in device-side compression diagnostics carried in
    the metrics pytree — `compression_err_sq` (‖ḡ − D‖², the distance
    between the uncompressed mean gradient and the wire's aggregated
    direction), `direction_norm_sq`, and the shift-table norms. Everything
    is pure jnp riding reductions GSPMD already does, no extra
    collectives; default OFF so the traced step's jaxpr is unchanged
    (pinned by the analysis census).
    """
    if eta is not None and local_steps == 1:
        raise ValueError("eta is the NASTYA server stepsize and requires "
                         "local_steps > 1 (with one local step the server "
                         "stepsize IS lr; Algorithms 2-3)")
    if elastic and local_steps > 1:
        raise ValueError(
            "elastic=True requires local_steps == 1: a NASTYA epoch "
            "consumes a full local mini-epoch per client, so a mid-epoch "
            "straggler has no well-defined RR rewind point")
    mcaxes = _client_axes(mesh)
    m = num_clients(mesh)
    agg = configure_agg(agg, mesh, local_steps)
    n_pods_ = _outer_ranks(agg)
    clients_per_pod = m // n_pods_
    gamma = lr
    server_lr = (eta if eta is not None else gamma * local_steps) \
        if local_steps > 1 else lr
    opt = _make_optimizer(optimizer, server_lr)
    # MoE configurations also return the routed-row counts of the round
    moe_stats = bool(cfg.num_experts)
    loss_fn = partial(transformer.loss_fn, cfg=cfg, remat=remat,
                      unroll=unroll, ce=ce, seq_shard=seq_shard,
                      with_stats=moe_stats)
    stateful = agg.rule.has_shifts  # diana / diana_rr / ef keep wire memory
    slotted = agg.rule.slotted
    nslots = agg.n_slots if slotted else 0  # 0 = tables carry no slot axis
    pod_nslots = agg._pod_slots if slotted else 0

    abstract = abstract_train_state(cfg, agg, m, optimizer=optimizer,
                                    mesh=mesh, local_steps=local_steps)
    pspecs = sharding.param_specs(abstract.params, mesh=mesh)
    stacked_specs = jax.tree.map(lambda s: P(mcaxes, *s), pspecs)
    pod_axis = agg.pod_axes  # leading axis of per-pod trees
    podded_specs = (sharding.podded_specs(abstract.params, pod_axis,
                                          mesh=mesh)
                    if pod_axis else pspecs)
    all_axes = set(mesh.axis_names)

    def manual(f, in_specs, out_specs):
        """Fully-manual shard_map (every axis manual) — the wire region."""
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names=all_axes,
                             check_vma=False)

    # spec trees matching the (possibly None) state fields; slotted tables
    # carry a replicated n_slots axis after the client/pod axis
    def tspec(tree, spec_tree):
        return None if tree is None else spec_tree
    shifts_sp = tspec(abstract.shifts,
                      sharding.shifts_specs(abstract.params, mcaxes,
                                            mesh=mesh, n_slots=nslots))
    slotted_sp = sharding.slotted_specs(abstract.params, mesh=mesh,
                                        n_slots=nslots)
    podded_slot_sp = (sharding.podded_specs(abstract.params, pod_axis,
                                            mesh=mesh, n_slots=nslots)
                      if pod_axis else slotted_sp)
    ms_sp = tspec(abstract.mean_shift,
                  podded_slot_sp if pod_axis else slotted_sp)
    psh_sp = tspec(abstract.pod_shifts,
                   sharding.podded_specs(abstract.params, pod_axis,
                                         mesh=mesh, n_slots=pod_nslots)
                   if pod_axis else None)
    pms_sp = tspec(abstract.pod_mean_shift,
                   sharding.slotted_specs(abstract.params, mesh=mesh,
                                          n_slots=pod_nslots))

    strip = lambda t: None if t is None else jax.tree.map(lambda x: x[0], t)
    stack = lambda t: None if t is None else jax.tree.map(
        lambda x: x[None], t)
    strip_pod = strip if pod_axis else (lambda t: t)
    stack_pod = stack if pod_axis else (lambda t: t)

    def grads_and_loss(params_stacked, batch_c):
        """Per-client (loss, grad, MoE counts) under GSPMD: vmap over the
        client dim. The counts, maxed over the clients (`max_expert_rows`)
        or summed (the others), are {} without experts."""
        with jax.named_scope("client_grads"):
            if not moe_stats:
                losses, g = jax.vmap(
                    lambda p, b: jax.value_and_grad(loss_fn)(p, b)
                )(params_stacked, batch_c)
                return losses, g, {}
            (losses, st), g = jax.vmap(
                lambda p, b: jax.value_and_grad(loss_fn, has_aux=True)(p, b)
            )(params_stacked, batch_c)
            return losses, g, {
                k: (jnp.max if k == "moe_max_expert_rows" else jnp.sum)(v)
                for k, v in st.items()}

    def broadcast_clients(tree):
        """params -> (M, *shape) client-stacked view (replication, no copy
        per device: the leading dim shards over the client axes)."""
        out = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (m,) + p.shape), tree)
        return jax.lax.with_sharding_constraint(
            out, jax.tree.map(lambda s: NamedSharding(mesh, s), stacked_specs))

    # -- wire regions (fully-manual shard_map bodies) --------------------------

    def full_wire_fn(g, shifts, mean_shift, pod_shifts, pod_mean_shift, kd,
                     slot, w=None):
        """Composed two-level exchange (the local_steps == 1 round).

        `w` is this rank's (1,)-block of the elastic weights vector (spec
        P(mcaxes): one scalar per client rank), or None on the non-elastic
        path — the two variants compile to different graphs but the weight
        only ever scales the compressed message into the collective mean."""
        g = strip(g)
        dstate = DianaState(strip(shifts), strip_pod(mean_shift),
                            strip_pod(pod_shifts), pod_mean_shift) \
            if stateful else None
        direction, nd = agg.aggregate(g, dstate,
                                      jax.random.wrap_key_data(kd), slot=slot,
                                      weight=None if w is None else w[0])
        if stateful:
            return (direction, stack(nd.shifts), stack_pod(nd.mean_shift),
                    stack_pod(nd.pod_shifts), nd.pod_mean_shift)
        return direction, shifts, mean_shift, pod_shifts, pod_mean_shift

    wire_out_specs = (pspecs, shifts_sp, ms_sp, psh_sp, pms_sp)
    if elastic:
        full_wire = manual(
            full_wire_fn,
            in_specs=(stacked_specs, shifts_sp, ms_sp, psh_sp, pms_sp, P(),
                      P(), P(mcaxes)),
            out_specs=wire_out_specs,
        )
    else:
        _full_wire = manual(
            lambda g, sh, ms, psh, pms, kd, slot: full_wire_fn(
                g, sh, ms, psh, pms, kd, slot),
            in_specs=(stacked_specs, shifts_sp, ms_sp, psh_sp, pms_sp, P(),
                      P()),
            out_specs=wire_out_specs,
        )
        full_wire = lambda g, sh, ms, psh, pms, kd, slot, w: _full_wire(
            g, sh, ms, psh, pms, kd, slot)

    def local_wire_fn(g, shifts, mean_shift, kd, slot):
        """Inner (intra-pod) exchange — one NASTYA local step's psum.

        `slot` arrives per-pod (spec P(pod_axis)): the micro-batch's shared
        batch index after the pod's own micro-epoch permutation."""
        g = strip(g)
        dstate = DianaState(strip(shifts), strip_pod(mean_shift)) \
            if stateful else None
        direction, nd = agg.aggregate_local(g, dstate,
                                            jax.random.wrap_key_data(kd),
                                            slot=slot[0])
        new_shifts, new_ms = (stack(nd.shifts), stack_pod(nd.mean_shift)) \
            if stateful else (shifts, mean_shift)
        # direction is identical on every rank of a pod; emit the pod block
        # (local_wire only exists on NASTYA paths, where pod_axis is set)
        return stack(direction), new_shifts, new_ms

    pod_lead = P(pod_axis) if pod_axis else P()
    local_wire = manual(
        local_wire_fn,
        in_specs=(stacked_specs, shifts_sp, ms_sp, P(), pod_lead),
        out_specs=(podded_specs, shifts_sp, ms_sp),
    )

    def pod_wire_fn(g_pod, pod_shifts, pod_mean_shift, kd):
        """Outer (inter-pod) exchange of the NASTYA epoch gradient (no batch
        slot — per-slot rules use table row 0 here)."""
        g = strip_pod(g_pod) if pod_axis else strip(g_pod)
        dstate = DianaState(None, None, strip_pod(pod_shifts),
                            pod_mean_shift) if stateful else None
        direction, nd = agg.aggregate_pod(g, dstate,
                                          jax.random.wrap_key_data(kd))
        if stateful:
            return direction, stack_pod(nd.pod_shifts), nd.pod_mean_shift
        return direction, pod_shifts, pod_mean_shift

    pod_wire = manual(
        pod_wire_fn,
        in_specs=(podded_specs, psh_sp, pms_sp, P()),
        out_specs=(pspecs, psh_sp, pms_sp),
    )

    # -- the step ---------------------------------------------------------------

    def _sq_norm(tree):
        """Σ‖leaf‖² in f32 — pure jnp, trace-safe."""
        return sum((jnp.sum(jnp.square(x.astype(jnp.float32)))
                    for x in jax.tree.leaves(tree)), jnp.float32(0.0))

    def _debug_extras(g_stacked, direction, new_shifts, new_ms):
        """Opt-in compression diagnostics: ‖ḡ − D‖² plus wire-state norms.

        ḡ is the uncompressed mean over the stacked leading axis (clients,
        or pods in NASTYA mode) — a reduction GSPMD lowers exactly like the
        wire's own mean, so no new collective patterns appear."""
        g_mean = jax.tree.map(
            lambda x: jnp.mean(x.astype(jnp.float32), axis=0), g_stacked)
        err = sum(
            (jnp.sum(jnp.square(gm - d.astype(jnp.float32)))
             for gm, d in zip(jax.tree.leaves(g_mean),
                              jax.tree.leaves(direction))),
            jnp.float32(0.0))
        return {"compression_err_sq": err,
                "direction_norm_sq": _sq_norm(direction),
                "shift_norm_sq": _sq_norm(new_shifts),
                "mean_shift_norm_sq": _sq_norm(new_ms)}

    def nastya_epoch(state: TrainState, batch, rkey, slots):
        """local_steps local RR mini-epochs per pod + one inter-pod round."""
        bsz = jax.tree.leaves(batch)[0].shape[0] // (m * local_steps)
        batch_r = jax.tree.map(
            lambda x: x.reshape((m, local_steps, bsz) + x.shape[1:]), batch)
        bspecs = jax.tree.map(
            lambda x: P(mcaxes, *(None,) * (x.ndim - 1)), batch_r)

        def permute_fn(b, sl, kd):
            # per-pod RR order over the local micro-epochs (Alg. 4 line 5);
            # device-local gather — every rank of a pod draws the same
            # order. The shared slot indices ride the same permutation so
            # per-slot shift tables stay aligned with the batches consumed.
            key = jax.random.wrap_key_data(kd)
            for ax in pod_axis:
                key = jax.random.fold_in(key, lax.axis_index(ax))
            perm = jax.random.permutation(key, local_steps)
            return jax.tree.map(lambda x: x[:, perm], b), sl[perm][None]

        batch_r, slots_pod = manual(
            permute_fn, in_specs=(bspecs, P(), P()),
            out_specs=(bspecs, P(pod_axis if pod_axis else None, None)))(
            batch_r, slots,
            jax.random.key_data(
                jax.random.fold_in(rkey, salts.NASTYA_PERM_SALT)))
        xs = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), batch_r)
        slot_cols = jnp.moveaxis(slots_pod, 1, 0)  # (local_steps, n_pods)

        x_pods = jax.lax.with_sharding_constraint(
            jax.tree.map(
                lambda p: jnp.broadcast_to(p[None], (n_pods_,) + p.shape),
                state.params),
            jax.tree.map(lambda s: NamedSharding(mesh, s), podded_specs))

        def body(carry, inp):
            x, shifts, mean_shift = carry
            batch_j, slot_j, t = inp
            x_clients = jax.lax.with_sharding_constraint(
                jax.tree.map(
                    lambda p: jnp.repeat(p, clients_per_pod, axis=0), x),
                jax.tree.map(lambda s: NamedSharding(mesh, s), stacked_specs))
            losses, g, stats = grads_and_loss(x_clients, batch_j)
            kd = jax.random.key_data(
                jax.random.fold_in(rkey, salts.NASTYA_LOCAL_SALT + t))
            with jax.named_scope("wire"):
                direction, shifts, mean_shift = local_wire(
                    g, shifts, mean_shift, kd, slot_j)
            x = jax.tree.map(
                lambda xi, d: (xi.astype(jnp.float32)
                               - gamma * d.astype(jnp.float32)
                               ).astype(xi.dtype), x, direction)
            return (x, shifts, mean_shift), (jnp.mean(losses), stats)

        (x_pods, new_shifts, new_ms), (losses, stats) = lax.scan(
            body, (x_pods, state.shifts, state.mean_shift),
            (xs, slot_cols, jnp.arange(local_steps)))

        # g_pod = (x_t - x_t^n) / (gamma * n)   (Alg. 4/5 line 7)
        g_pod = jax.tree.map(
            lambda p, xn: (p[None].astype(jnp.float32)
                           - xn.astype(jnp.float32))
            / (gamma * local_steps), state.params, x_pods)
        with jax.named_scope("wire"):
            direction, new_psh, new_pms = pod_wire(
                g_pod, state.pod_shifts, state.pod_mean_shift,
                jax.random.key_data(rkey))
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree.leaves(g_pod)) / n_pods_)
        extras = (_debug_extras(g_pod, direction, new_shifts, new_ms)
                  if debug_metrics else {})
        if stats:
            extras.update(
                {k: (jnp.max if k == "moe_max_expert_rows" else jnp.sum)(v)
                 for k, v in stats.items()})
        return (direction, new_shifts, new_ms, new_psh, new_pms,
                jnp.mean(losses), gnorm, extras)

    def flat_round(state: TrainState, batch, rkey, slots, weights):
        """One communication round (Algorithms 2-3 / the composed wire)."""
        bsz = jax.tree.leaves(batch)[0].shape[0] // m
        batch_c = jax.tree.map(
            lambda x: x.reshape((m, bsz) + x.shape[1:]), batch)
        losses, g, stats = grads_and_loss(broadcast_clients(state.params),
                                          batch_c)
        with jax.named_scope("wire"):
            direction, new_shifts, new_ms, new_psh, new_pms = full_wire(
                g, state.shifts, state.mean_shift, state.pod_shifts,
                state.pod_mean_shift, jax.random.key_data(rkey), slots[0],
                weights)
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree.leaves(g)) / m)
        extras = {**stats, **(_debug_extras(g, direction, new_shifts, new_ms)
                              if debug_metrics else {})}
        return (direction, new_shifts, new_ms, new_psh, new_pms,
                jnp.mean(losses), gnorm, extras)

    def check_batch(batch):
        """The batch contract (fed by data.pipeline.make_batch_stream):
        every leaf client-major with m * local_steps * b leading rows."""
        leads = {x.shape[0] for x in jax.tree.leaves(batch)}
        if not leads:
            raise ValueError("empty batch: the step needs at least one "
                             "client-major (m * local_steps * b)-row leaf")
        if len(leads) != 1:
            raise ValueError(
                f"batch leaves disagree on leading rows {sorted(leads)} — "
                "every modality must ride the same client-major row stream")
        rows = leads.pop()
        if rows == 0 or rows % (m * local_steps) != 0:
            raise ValueError(
                f"batch has {rows} leading rows, not divisible by "
                f"m*local_steps = {m}*{local_steps} — the step consumes "
                "client-major (m * local_steps * b)-row batches; feed it "
                "with data.pipeline.make_batch_stream")

    def step(state: TrainState, batch, key, slots, weights=None):
        check_batch(batch)
        if slots is None:
            slots = jnp.zeros((local_steps,), jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        if slots.shape != (local_steps,):
            raise ValueError(
                f"slots must be a ({local_steps},) int32 vector of shared "
                f"batch indices (one per local micro-step), got "
                f"{slots.shape} — see data.pipeline.shared_slots_for_step")
        if elastic:
            weights = jnp.asarray(weights, jnp.float32)
            if weights.shape != (m,):
                raise ValueError(
                    f"elastic weights must be an ({m},) f32 vector (one "
                    f"participation weight per client rank), got "
                    f"{weights.shape}")
        rkey = jax.random.fold_in(key, state.step)
        if local_steps > 1:
            (direction, new_shifts, new_ms, new_psh, new_pms, loss,
             gnorm, extras) = nastya_epoch(state, batch, rkey, slots)
        else:
            (direction, new_shifts, new_ms, new_psh, new_pms, loss,
             gnorm, extras) = flat_round(state, batch, rkey, slots,
                                         weights if elastic else None)
        with jax.named_scope("server_update"):
            updates, new_opt = opt.update(
                jax.tree.map(lambda d: d.astype(jnp.float32), direction),
                state.opt_state, state.params)
            new_params = optim.apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": gnorm, **extras}
        return TrainState(new_params, new_shifts, new_ms, state.step + 1,
                          new_opt, new_psh, new_pms), metrics

    shardings = train_state_shardings(mesh, abstract, agg)
    batch_sh = lambda batch: jax.tree.map(
        lambda x: NamedSharding(mesh, P(mcaxes, *(None,) * (x.ndim - 1))),
        batch)
    # signature grows right-to-left: per-slot methods append the round's
    # shared slot vector, elastic steps append the (m,) weights vector last
    if slotted and elastic:
        jitted = jax.jit(
            step,
            in_shardings=(shardings, None, None, None, None),
            out_shardings=(shardings, None),
            donate_argnums=(0,),
        )
    elif slotted:
        jitted = jax.jit(
            lambda state, batch, key, slots: step(state, batch, key, slots),
            in_shardings=(shardings, None, None, None),
            out_shardings=(shardings, None),
            donate_argnums=(0,),
        )
    elif elastic:
        jitted = jax.jit(
            lambda state, batch, key, weights: step(state, batch, key, None,
                                                    weights),
            in_shardings=(shardings, None, None, None),
            out_shardings=(shardings, None),
            donate_argnums=(0,),
        )
    else:
        jitted = jax.jit(
            lambda state, batch, key: step(state, batch, key, None),
            in_shardings=(shardings, None, None),
            out_shardings=(shardings, None),
            donate_argnums=(0,),
        )
    return jitted, abstract, shardings, batch_sh


# ---------------------------------------------------------------------------
# inference steps (pure GSPMD)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig, mesh, *, cache_len: int,
                      remat: bool = True, unroll: bool = False):
    caxes = _client_axes(mesh)

    def prefill(params, batch):
        return transformer.prefill(params, batch, cfg, cache_len=cache_len,
                                   remat=remat, unroll=unroll)

    def lower_args(params_abs, batch_abs):
        psh = sharding.param_shardings(mesh, params_abs)
        bsh = jax.tree.map(
            lambda x: NamedSharding(mesh, P(caxes, *(None,) * (x.ndim - 1))),
            batch_abs,
        )
        cache_abs = jax.eval_shape(prefill, params_abs, batch_abs)[1]
        csh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            sharding.cache_specs(cache_abs, caxes, mesh=mesh,
                                 n_clients=num_clients(mesh)),
        )
        jitted = jax.jit(prefill, in_shardings=(psh, bsh),
                         out_shardings=(None, csh))
        return jitted

    return prefill, lower_args


def make_serve_step(cfg: ArchConfig, mesh, *, unroll: bool = False):
    caxes = _client_axes(mesh)

    def serve(params, cache, tokens, pos):
        return transformer.decode_step(params, cache, tokens, pos, cfg,
                                       unroll=unroll)

    def lower_args(params_abs, cache_abs, tokens_abs):
        psh = sharding.param_shardings(mesh, params_abs)
        b = tokens_abs.shape[0]
        n_cl = num_clients(mesh)
        csh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            sharding.cache_specs(cache_abs, caxes, mesh=mesh,
                                 n_clients=n_cl),
        )
        tsh = NamedSharding(mesh, P(caxes) if b >= n_cl else P())
        jitted = jax.jit(
            serve,
            in_shardings=(psh, csh, tsh, NamedSharding(mesh, P())),
            out_shardings=(None, csh),
            donate_argnums=(1,),
        )
        return jitted, (psh, csh, tsh)

    return serve, lower_args
