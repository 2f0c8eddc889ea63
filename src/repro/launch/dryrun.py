import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this proves, without hardware:
  - the sharding config is coherent (no mismatched collectives),
  - the per-device memory fits (memory_analysis),
  - and it yields the FLOPs/bytes/collective numbers for EXPERIMENTS.md
    (§Dry-run, §Roofline).

Usage:
  python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results.jsonl
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import telemetry

from repro.configs import (
    ARCH_NAMES,
    INPUT_SHAPES,
    get_config,
    input_specs,
    shape_supported,
)
from repro.core import salts
from repro.core.dist import CompressedAggregation
from repro.data.pipeline import abstract_stream_batch
from repro.launch import steps
from repro.launch.hlo_analysis import (
    Roofline,
    collective_stats,
    memory_summary,
    roofline_from_compiled,
)
from repro.models import flags
from repro.launch.mesh import make_production_mesh, num_clients
from repro.models import transformer


def _compile_one(cfg, shape, mesh, agg, *, remat, unroll: bool,
                 ce: str = "gather", seq_shard: bool = True,
                 local_steps: int = 1, elastic: bool = False):
    """Lower + compile the step this shape exercises for config `cfg`."""
    specs = input_specs(cfg, shape)
    if shape.kind == "train":
        jitted, abstract, shardings, _ = steps.make_train_step(
            cfg, mesh, agg=agg, remat=remat, unroll=unroll, ce=ce,
            seq_shard=seq_shard, local_steps=local_steps, elastic=elastic
        )
        # the batch contract of data.pipeline.make_batch_stream: client-major
        # m * local_steps * b rows on every leaf
        batch = abstract_stream_batch(specs["batch"], local_steps)
        key = jax.ShapeDtypeStruct(
            (), salts.root_key(0, salts.ROUNDS_KEY_SALT).dtype)
        # the buffered-async wire weights vector (elastic step only)
        extra = ((jax.ShapeDtypeStruct((num_clients(mesh),), jnp.float32),)
                 if elastic else ())
        with jax.set_mesh(mesh):
            if agg.rule.slotted:  # per-slot methods take the slot vector
                slots = jax.ShapeDtypeStruct((local_steps,), jnp.int32)
                lowered = jitted.lower(abstract, batch, key, slots, *extra)
            else:
                lowered = jitted.lower(abstract, batch, key, *extra)
    elif shape.kind == "prefill":
        prefill, lower_args = steps.make_prefill_step(
            cfg, mesh, cache_len=shape.seq_len, remat=remat, unroll=unroll
        )
        params_abs = jax.eval_shape(
            lambda: transformer.init_params(
                salts.root_key(0, salts.PARAMS_KEY_SALT), cfg)
        )
        jitted = lower_args(params_abs, specs["batch"])
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, specs["batch"])
    else:  # decode
        serve, lower_args = steps.make_serve_step(cfg, mesh, unroll=unroll)
        params_abs = jax.eval_shape(
            lambda: transformer.init_params(
                salts.root_key(0, salts.PARAMS_KEY_SALT), cfg)
        )
        jitted, _ = lower_args(params_abs, specs["cache"], specs["tokens"])
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_abs, specs["cache"],
                                   specs["tokens"], specs["pos"])
    return lowered.compile()


def _probe_cfg(cfg, k: int):
    changes = {"num_layers": k}
    if cfg.encoder_layers:
        changes["encoder_layers"] = k
    return dataclasses.replace(cfg, **changes)


def fleet_smoke(cfg, mesh, agg, clients: int, *, local_steps: int = 1,
                buffer_k: int | None = None, chaos_dropout: float = 0.0,
                chaos_seed: int = 0, data_store: str | None = None):
    """Fleet sizing at population scale C — NO population-sized allocation.

    Proves, next to the compiled step, that the fleet layer scales: the
    cohort walk draws valid mesh-rank-sized cohorts, the host store's
    byte footprint is a closed-form estimate (`estimate_nbytes`), and the
    per-round device shift memory is O(cohort) — every TrainState shift
    table is keyed on the MESH client count, so the population size must
    not appear in any device shape (DESIGN.md §3.9).
    """
    import numpy as np

    from repro.fleet import ClientStateStore, CohortSampler
    from repro.launch import steps

    m = num_clients(mesh)
    agg_c = steps.configure_agg(agg, mesh, local_steps)
    abstract = steps.abstract_train_state(cfg, agg, m, mesh=mesh,
                                          local_steps=local_steps)
    cohorts = CohortSampler(clients, m, seed=0)
    for r in (0, 1, clients // m):  # incl. a fleet-epoch-straddling round
        c = cohorts.cohort_for_round(r)
        assert c.shape == (m,) and 0 <= c[0] and c[-1] < clients
        assert (np.diff(c) > 0).all(), "cohorts must be sorted + distinct"
    # O(cohort) device memory: every per-client device table is keyed on
    # the MESH client count, never the population (checking the client
    # leading axis specifically — bare `clients in shape` membership would
    # false-positive whenever C coincides with a model dimension)
    shift_leaves = [] if abstract.shifts is None else jax.tree.leaves(
        abstract.shifts)
    for leaf in shift_leaves:
        assert leaf.shape[0] == m, (
            f"device shift table leading dim {leaf.shape} != cohort size "
            f"{m} — per-client state must stay O(cohort)")
    device_shift_bytes = sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in shift_leaves)
    store_bytes = ClientStateStore.estimate_nbytes(
        abstract.params, clients, agg_c.rule, n_slots=agg_c.n_slots,
        dtype=agg_c.shift_dtype)
    out = {"population": clients, "cohort": m,
           "cohort_mode": "rr",
           "rounds_per_fleet_epoch": clients / m,
           "device_shift_bytes": device_shift_bytes,
           "store_bytes": store_bytes}
    if buffer_k is not None or chaos_dropout > 0:
        # host-side buffered-async planning at population scale: the
        # planner is O(cohort) per round no matter how big C is, and the
        # probe shows how many completers the K-of-m trigger keeps
        from repro.fleet import AsyncPlanner, ChaosConfig

        planner = AsyncPlanner(
            m, buffer_k=buffer_k,
            chaos=ChaosConfig(dropout=chaos_dropout, seed=chaos_seed))
        probed = [planner(r, cohorts.cohort_for_round(r))
                  for r in range(16)]
        done = [int(p.completes.sum()) for p in probed]
        out["async"] = {"buffer_k": planner.buffer_k,
                        "chaos_dropout": chaos_dropout,
                        "rounds_probed": len(probed),
                        "mean_completers": float(np.mean(done)),
                        "min_completers": int(min(done))}
    if data_store is not None:
        # paged-data probe at population scale: a sparse on-disk store (no
        # shard file until written — absent shards read as zeros, so a
        # 10^5-client layout costs one spec file), a REAL paged
        # CohortStream walking 8 rounds including a fleet-epoch straddle,
        # and the §3.11 invariant: resident bytes stay under the lookahead
        # window bound no matter how big C is
        from repro.data.paging import ClientDataStore, LookaheadPager
        from repro.data.pipeline import CohortStream
        from repro.data.reshuffle import ReshuffleSampler

        n_probe, b_probe = 2, 1
        dstore = ClientDataStore.create(
            data_store, clients,
            {"tokens": jax.ShapeDtypeStruct((n_probe, b_probe, 64),
                                            jnp.int32)},
            shard_size=512)
        pager = LookaheadPager(dstore, lookahead=1)
        # start 3 rounds before the fleet-epoch boundary so the 8-round
        # walk crosses it (straddle cohorts deconflict, counts resume
        # closed-form)
        start = max(0, clients // m - 3)
        stream = CohortStream(None, ReshuffleSampler(clients, n_probe,
                                                     seed=1),
                              cohorts, paged=pager, start_round=start)
        with stream:
            for _ in range(8):
                fr = next(stream)
                assert fr.batch["tokens"].shape[0] == m * b_probe
        bound = pager.resident_bound_nbytes(m)
        assert pager.resident_nbytes() <= bound, (
            f"paged resident set {pager.resident_nbytes()}B exceeds the "
            f"lookahead window bound {bound}B")
        out["paging"] = {"path": data_store,
                         "num_shards": dstore.num_shards,
                         "store_nbytes": dstore.nbytes,
                         "resident_nbytes": pager.resident_nbytes(),
                         "resident_bound_nbytes": bound,
                         **{k: pager.stats()[k]
                            for k in ("hits", "misses", "evictions")}}
    return out


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool,
               agg_method: str = "diana", agg_wire: str = "shared",
               wire_dtype: str = "f32",
               fraction: float = 0.02, remat="full", ce: str = "gather",
               seq_shard: bool = True, probes: bool = True,
               local_steps: int = 1, clients: int | None = None,
               buffer_k: int | None = None, chaos_dropout: float = 0.0,
               data_store: str | None = None,
               extra_tags: dict | None = None):
    """Lower + compile one (arch, shape, mesh). Returns a result dict.

    Protocol (DESIGN.md §6): the FULL-depth model is compiled with the
    production `lax.scan` layer loop — that is the must-succeed dry-run and
    the source of `memory_analysis()` (scan gives true buffer reuse). XLA's
    cost model counts loop bodies once, so FLOPs/bytes/collective terms come
    from two shallow FULLY-UNROLLED depth probes (k=1, 2 layers, inner scans
    unrolled too) extrapolated affinely to the real depth — every per-layer
    term (compute, HBM traffic, gradient-compression collectives) is exactly
    affine in layer count.
    """
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    m = num_clients(mesh)
    # diana_rr at the dry-run scale: a representative 8-slot shift table
    # (the real n comes from the data; the compile only needs the layout)
    agg = CompressedAggregation(method=agg_method, wire=agg_wire,
                                fraction=fraction, wire_dtype=wire_dtype,
                                n_slots=8 if agg_method == "diana_rr" else 1)
    n_dev = mesh.devices.size

    # buffered-async knobs compile the ELASTIC step (trailing per-rank
    # weights vector) — the variant AsyncFleetRunner drives
    elastic = buffer_k is not None or chaos_dropout > 0

    # 1) full-depth scan compile: the dry-run proper + memory analysis
    t0 = time.time()
    flags.set_unroll_inner_scans(False)
    compiled_full = _compile_one(cfg, shape, mesh, agg, remat=remat,
                                 unroll=False, ce=ce, seq_shard=seq_shard,
                                 local_steps=local_steps, elastic=elastic)
    t_full = time.time() - t0
    mem = memory_summary(compiled_full)
    roof_scan = roofline_from_compiled(compiled_full, n_dev)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "n_devices": n_dev,
        "clients": m,
        "agg": {"method": agg_method, "wire": agg_wire, "fraction": fraction,
                "wire_dtype": wire_dtype},
        "remat": str(remat),
        "ce": ce,
        "seq_shard": seq_shard,
        "local_steps": local_steps,
        "elastic": elastic,
        "compile_s": round(t_full, 1),
        "memory": mem,
        "roofline_scan_raw": roof_scan.as_dict(),
        "model_params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if clients is not None and shape.kind == "train":
        result["fleet"] = fleet_smoke(cfg, mesh, agg, clients,
                                      local_steps=local_steps,
                                      buffer_k=buffer_k,
                                      chaos_dropout=chaos_dropout,
                                      data_store=data_store)

    # 2) depth probes (unrolled) -> affine extrapolation of cost terms
    if probes:
        t1 = time.time()
        flags.set_unroll_inner_scans(True)
        try:
            probes_raw = {}
            for k in (1, 2):
                ck = _compile_one(_probe_cfg(cfg, k), shape, mesh, agg,
                                  remat=remat, unroll=True, ce=ce,
                                  seq_shard=seq_shard,
                                  local_steps=local_steps, elastic=elastic)
                probes_raw[k] = roofline_from_compiled(ck, n_dev)
                result.setdefault("top_collectives", {})[k] = [
                    (f"{b:.3e}", kind, shp)
                    for b, kind, shp in collective_stats(ck.as_text()).top[:5]
                ]
        finally:
            flags.set_unroll_inner_scans(False)
        L = cfg.num_layers
        def extrap(term):
            f1, f2 = getattr(probes_raw[1], term), getattr(probes_raw[2], term)
            return max(f1 + (L - 1) * (f2 - f1), f1)
        roof = Roofline(
            flops=extrap("flops"),
            hbm_bytes=extrap("hbm_bytes"),
            collective_bytes=extrap("collective_bytes"),
            n_devices=n_dev,
        )
        result["probe_s"] = round(time.time() - t1, 1)
        result["probes"] = {k: v.as_dict() for k, v in probes_raw.items()}
        result["roofline"] = roof.as_dict()
    else:
        result["roofline"] = roof_scan.as_dict()

    if extra_tags:
        result.update(extra_tags)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) combination")
    ap.add_argument("--agg", "--method", default="diana",
                    choices=("dense", "q", "diana", "diana_rr", "ef"))
    ap.add_argument("--wire", default="shared",
                    choices=("shared", "independent"))
    ap.add_argument("--wire-dtype", default="f32",
                    choices=("f32", "bf16", "packed8", "packed4"),
                    help="transport dtype for the shared wire slab "
                         "(DESIGN.md §3.13)")
    ap.add_argument("--fraction", type=float, default=0.02)
    ap.add_argument("--remat", default="full", choices=("full", "dots", "none"))
    ap.add_argument("--ce", default="gather", choices=("streaming", "gather"))
    ap.add_argument("--seq-shard", dest="seq_shard", action="store_true", default=True)
    ap.add_argument("--no-seq-shard", dest="seq_shard", action="store_false")
    ap.add_argument("--local-steps", type=int, default=1,
                    help="NASTYA local mini-epochs per round (pod granularity)")
    ap.add_argument("--clients", type=int, default=None,
                    help="fleet population size: record cohort-walk + "
                         "state-store sizing next to the compile and assert "
                         "device shift memory stays O(cohort) — DESIGN.md "
                         "§3.9 (train shapes only)")
    ap.add_argument("--buffer-k", type=int, default=None,
                    help="compile the buffered-async ELASTIC step and probe "
                         "the K-of-m participation plan host-side "
                         "(DESIGN.md §3.10; train shapes with --clients)")
    ap.add_argument("--chaos-dropout", type=float, default=0.0,
                    help="per-round client dropout probability for the "
                         "async participation probe")
    ap.add_argument("--data-store", default=None,
                    help="probe the out-of-core paged-data path: lay a "
                         "sparse per-client data store under this directory "
                         "and walk a real paged CohortStream across a "
                         "fleet-epoch boundary, asserting host residency "
                         "stays under the lookahead-window bound "
                         "(DESIGN.md §3.11; train shapes with --clients)")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the unrolled depth probes (report raw scan "
                         "cost terms, which count loop bodies once)")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--tag", default=None, help="label stored with results")
    ap.add_argument("--telemetry", default=None, metavar="JSONL",
                    help="stream dry-run telemetry (compile spans; with "
                         "--data-store the fleet smoke's assemble/page_in "
                         "spans and pager counters) to this JSONL file")
    ap.add_argument("--trace", default=None, metavar="JSON",
                    help="also export a Chrome/Perfetto trace at exit")
    args = ap.parse_args(argv)

    tpath = args.telemetry
    if args.trace and not tpath:
        base = (args.trace[:-5] if args.trace.endswith(".json")
                else args.trace)
        tpath = base + ".telemetry.jsonl"
    if tpath is not None:
        telemetry.install(telemetry.MetricsSink(tpath))
        telemetry.run_meta({"tool": "dryrun", "agg": args.agg,
                            "wire_dtype": args.wire_dtype,
                            "clients": args.clients,
                            "data_store": bool(args.data_store)})

    pairs = (
        [(a, s) for a in ARCH_NAMES for s in INPUT_SHAPES]
        if args.all else [(args.arch, args.shape)]
    )
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    try:
        for arch, shape in pairs:
            for multi in meshes:
                try:
                    with telemetry.span("compile", arch=arch, shape=shape):
                        res = lower_pair(
                            arch, shape, multi_pod=multi,
                            agg_method=args.agg,
                            agg_wire=args.wire, wire_dtype=args.wire_dtype,
                            fraction=args.fraction,
                            remat=args.remat, ce=args.ce,
                            seq_shard=args.seq_shard,
                            probes=not args.no_probes,
                            local_steps=args.local_steps,
                            clients=args.clients, buffer_k=args.buffer_k,
                            chaos_dropout=args.chaos_dropout,
                            data_store=args.data_store,
                            extra_tags={"tag": args.tag} if args.tag
                            else None,
                        )
                except Exception as e:  # a dry-run failure is a sharding bug
                    failures += 1
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                line = json.dumps(res)
                print(line, flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(line + "\n")
    finally:
        sink = telemetry.active()
        if sink is not None:
            telemetry.uninstall()
            sink.close()
            if args.trace:
                n = telemetry.write_trace(
                    telemetry.read_events(tpath), args.trace)
                print(f"trace -> {args.trace} ({n} trace events)",
                      file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
