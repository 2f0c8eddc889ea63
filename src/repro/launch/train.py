"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --steps 100 --agg diana --fraction 0.02 [--production-mesh]

The mesh is built from the attached devices, one federated client per
device (`make_attached_mesh`): m = 1 on one TPU chip, m = 4 on a four-chip
host. On a TPU it runs the arch's chip-share config (published widths,
depth cut to one chip's share, `configs.get_chip_config`) with full remat;
elsewhere it runs the `reduced()` config, without remat, for rehearsals.
This module sets no XLA flags: a CPU rehearsal forces its host devices on
its own command line (README.md). `--production-mesh` builds the 16x16 (or
2x16x16 with --multi-pod) mesh with the full config. Every piece is the
production path: per-client gradients, the paper's compressed wire, DIANA
shifts, the epoch-indexed RR batch stream (`data.pipeline`, DESIGN.md §3.7)
with double-buffered prefetch, and cursor-checkpointed resume (`--resume`
bit-reproduces the data stream).

`--clients C` (with C > the mesh client count) switches to the FLEET path
(DESIGN.md §3.9): each round samples a cohort of mesh-rank-many clients
from a C-client population (`--cohort-mode rr` walks a fresh population
permutation per fleet epoch — client-level RR; `with_replacement` is the
i.i.d. baseline), DIANA(-RR) shifts live in a host-sharded
`ClientStateStore` and only the cohort's slices touch the device, and
`--checkpoint/--resume` persist the store + fleet cursor so a resumed run
bit-reproduces an uninterrupted one. With C equal to the mesh client count
the fleet path bit-matches this file's full-participation loop.
"""
import os
import argparse
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry

from repro.checkpoint import load_meta, restore_train_state, save_pytree
from repro.checkpoint.io import (
    restore_fleet_checkpoint,
    save_fleet_checkpoint,
)
from repro.configs import ARCH_NAMES, get_chip_config, get_config, reduced
from repro.core import salts
from repro.core.dist import CompressedAggregation
from repro.data.paging import ClientDataStore, LookaheadPager
from repro.data.pipeline import make_batch_stream, shared_slots_for_step
from repro.data.reshuffle import ReshuffleSampler
from repro.data.tokens import synthetic_token_batches
from repro.fleet import (
    COHORT_MODES,
    LATE_POLICIES,
    AsyncFleetRunner,
    AsyncPlanner,
    ChaosConfig,
    CohortSampler,
    ClientStateStore,
    FleetRunner,
)
from repro.launch import steps
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import (
    make_attached_mesh,
    make_production_mesh,
    num_clients,
)
from repro.models.config import ArchConfig


def stub_modalities(cfg, m: int, n_batches: int, b: int, *, seed: int = 0):
    """Client-stacked VLM/audio stub leaves, (m, n, b, ...) like the tokens.

    Each (client, batch-slot) holds its own deterministic rows, so the
    stream's RR gather keeps modalities row-aligned with the tokens (the
    seed-era `tile_extra` handed every local micro-step byte-identical
    rows — indistinguishable from a misaligned stream in any test).
    """
    extras = {}
    rng = np.random.default_rng((seed, salts.MODALITY_STUB_SALT))
    if cfg.family == "vlm":
        extras["patches"] = rng.normal(
            size=(m, n_batches, b, cfg.vision_patches, cfg.d_model)
        ).astype(cfg.dtype)
    if cfg.is_encdec:
        extras["frames"] = rng.normal(
            size=(m, n_batches, b, cfg.encoder_seq, cfg.d_model)
        ).astype(cfg.dtype)
    return extras


def chaos_from_args(args) -> ChaosConfig:
    """The --chaos-* CLI surface -> one deterministic fault config."""
    return ChaosConfig(
        dropout=args.chaos_dropout, straggler=args.chaos_straggler,
        delay=args.chaos_delay, store_fail=args.chaos_store_fail,
        max_retries=args.chaos_retries, backoff=args.chaos_backoff,
        seed=args.chaos_seed)


def fleet_is_async(args) -> bool:
    """Buffered-async mode turns on when any async/chaos knob is set; a
    plain --clients run keeps the synchronous driver (and its compiled
    step) byte-identical to before."""
    chaos = chaos_from_args(args)
    return (args.buffer_k is not None or args.late == "drop"
            or chaos.dropout > 0 or chaos.straggler > 0
            or chaos.store_fail > 0)


def run_fleet(args, tr, b, callback=None):
    """The fleet (partial-participation) loop: C-client population, cohort
    of m mesh ranks per round, host state store (DESIGN.md §3.9).

    Without --data-store the synthetic population DATASET is materialized
    dense on the host (O(C * n * b * seq) — fine for demo scales). With
    --data-store PATH the dataset lives on disk as per-client rows
    (`repro.data.paging.ClientDataStore`) and each round's cohort pages in
    through the deterministic lookahead pager — host RSS is bounded by the
    lookahead window, not the population (DESIGN.md §3.11). Batches are
    bit-identical either way.
    """
    cfg, mesh, agg, m, n_batches = tr.cfg, tr.mesh, tr.agg, tr.m, tr.n_batches
    jitted, abstract, shardings, batch_sh = (tr.jitted, tr.abstract,
                                             tr.shardings, tr.batch_sh)
    C = args.clients
    data = {"tokens": np.asarray(synthetic_token_batches(
        vocab=cfg.vocab, seq_len=args.seq, batch=b,
        num_batches=n_batches, num_clients=C, seed=0))}
    data.update(stub_modalities(cfg, C, n_batches, b))
    sampler = ReshuffleSampler(C, n_batches, mode=args.sampling, seed=1)
    cohorts = CohortSampler(C, m, mode=args.cohort_mode, seed=2)
    store = ClientStateStore.create(
        abstract.params, C, agg.rule, n_slots=agg.n_slots,
        dtype=agg.shift_dtype, path=args.store_path)
    est = ClientStateStore.estimate_nbytes(
        abstract.params, C, agg.rule, n_slots=agg.n_slots,
        dtype=agg.shift_dtype)
    print(f"fleet: population {C}, cohort {m} ({args.cohort_mode}), "
          f"store {est/1e6:.1f}MB "
          + (f"mmap@{args.store_path}" if args.store_path else "host RAM")
          + " / O(cohort) device")

    pager = None
    if args.data_store:
        if os.path.exists(os.path.join(args.data_store, "data_store.json")):
            dstore = ClientDataStore.open(args.data_store)
        else:
            dstore = ClientDataStore.from_stacked(args.data_store, data)
        pager = LookaheadPager(dstore, state=store)
        print(f"data store: {dstore.nbytes/1e6:.1f}MB on disk "
              f"@{args.data_store} ({dstore.num_shards} shards x "
              f"{dstore.shard_size} clients), resident <= "
              f"{pager.resident_bound_nbytes(m)/1e6:.1f}MB")
        data = None

    use_async = fleet_is_async(args)
    chaos = chaos_from_args(args)
    async_spec = AsyncPlanner(
        m, buffer_k=args.buffer_k, late=args.late, discount=args.discount,
        chaos=chaos).spec() if use_async else None

    start_round = 0
    if args.resume:
        meta = load_meta(args.resume)
        fm = (meta.get("meta") or {}).get("fleet")
        if fm is None:
            raise SystemExit(f"{args.resume}: no fleet cursor in manifest — "
                             "not a fleet checkpoint?")
        if fm["sampler"] != sampler.spec() or \
                fm["cohort_sampler"] != cohorts.spec() or \
                fm["local_steps"] != args.local_steps:
            raise SystemExit(
                f"{args.resume}: checkpointed fleet walk {fm} does not "
                "match this run's samplers/local_steps — refusing to "
                "resume onto a different cohort walk")
        if fm.get("async") != async_spec:
            raise SystemExit(
                f"{args.resume}: checkpointed async/chaos plan "
                f"{fm.get('async')} does not match this run's "
                f"{async_spec} — the participation schedule is part of "
                "the walk; resume with the same --buffer-k/--late/"
                "--chaos-* flags")
        have_ds = None if pager is None else pager.data.spec()
        if fm.get("data_store") != have_ds:
            raise SystemExit(
                f"{args.resume}: checkpointed data-store layout "
                f"{fm.get('data_store')} does not match this run's "
                f"{have_ds} — resume with the same --data-store layout "
                "(page identities derive from it)")
        start_round = fm["round"]

    key = salts.root_key(0, salts.ROUNDS_KEY_SALT)
    with jax.set_mesh(mesh):
        if args.resume:
            state = restore_fleet_checkpoint(
                args.resume, abstract, shardings, store,
                data_store=None if pager is None else pager.data)
            print(f"resumed {args.resume} at round {start_round} "
                  f"(fleet epoch {fm['fleet_epoch']})")
        else:
            state = init_state(args, tr)
        if use_async:
            runner = AsyncFleetRunner(
                jitted, abstract, shardings, batch_sh, agg=agg, mesh=mesh,
                data=data, sampler=sampler, cohorts=cohorts, store=store,
                buffer_k=args.buffer_k, late=args.late,
                discount=args.discount, chaos=chaos,
                local_steps=args.local_steps, prefetch=args.prefetch,
                start_round=start_round, paged=pager)
            print(f"async: buffer K={runner._planner.buffer_k}/{m} "
                  f"late={args.late} chaos={chaos.spec()}")
        else:
            runner = FleetRunner(
                jitted, abstract, shardings, batch_sh, agg=agg, mesh=mesh,
                data=data, sampler=sampler, cohorts=cohorts, store=store,
                local_steps=args.local_steps, prefetch=args.prefetch,
                start_round=start_round, paged=pager)

        # monotonic rate over the stepping window only: start() fires after
        # restore + runner/stream construction, and the checkpoint write
        # below lands after the last report — neither folds into s/round
        reporter = telemetry.ConsoleReporter(
            unit="round", log_every=args.log_every, total=args.steps,
            start=start_round)

        def log(t, state, metrics):
            record_moe_rows(t, metrics)
            reporter.report(t, metrics, cohort=m)
            if callback is not None:
                callback(t, state, metrics)

        # the store owns every client's state and the runner puts the
        # cohort's rows on the device each round: holding the initial rows
        # as well would keep a second per-client table on the device
        state = state._replace(**{runner.shift_field: None})
        with runner:
            reporter.start()
            state = runner.run(state, key, args.steps - start_round,
                               callback=log)
            if args.checkpoint:
                save_fleet_checkpoint(
                    args.checkpoint, jax.device_get(state), store,
                    step=int(state.step),
                    meta={"fleet": runner.checkpoint_meta()},
                    data_store=None if pager is None else pager.data)
                print(f"fleet checkpoint -> {args.checkpoint} "
                      f"(round {runner.round})")
    return state


def build_parser() -> argparse.ArgumentParser:
    """The CLI surface (separate so tests can assert the module docstring's
    example flags stay parseable — flag/doc drift is a bug)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="stablelm-1.6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1,
                    help="client/local stepsize gamma")
    ap.add_argument("--local-steps", type=int, default=1,
                    help=">1 runs Q-NASTYA/DIANA-NASTYA at pod granularity: "
                         "that many local RR mini-epochs between rounds")
    ap.add_argument("--eta", type=float, default=None,
                    help="server stepsize for --local-steps>1 "
                         "(default gamma*local_steps = FedRR equivalence)")
    ap.add_argument("--agg", "--method",
                    choices=("diana", "q", "dense", "diana_rr", "ef"),
                    default="diana",
                    help="wire aggregation method; 'diana_rr' runs the "
                         "paper's per-slot shifts (Algorithm 3) and needs "
                         "--sampling rr_shared, 'ef' is error feedback")
    ap.add_argument("--wire", choices=("shared", "independent"), default="shared")
    ap.add_argument("--wire-dtype",
                    choices=("f32", "bf16", "packed8", "packed4"),
                    default="f32",
                    help="shared-wire slab transport: 'packed8'/'packed4' "
                         "bit-pack quantized levels and all_gather the byte "
                         "lattice + f32 scale sideband (DESIGN.md §3.13); "
                         "'bf16' halves the psum lanes")
    ap.add_argument("--wire-levels", type=int, default=None,
                    help="stochastic-quantization levels of the shared-wire "
                         "slab (packed wires default to their lane cap); "
                         "'f32' with levels moves the same quantized payload "
                         "as packed8/packed4 at 4 B/lane")
    # the paper's headline compression ratio (k/d ~= 0.02, Sec. 3) — must
    # stay in sync with the module-docstring example above
    ap.add_argument("--fraction", type=float, default=0.02)
    ap.add_argument("--pods", type=int, default=1,
                    help=">1 splits the attached devices into a (pods, "
                         "n/pods, 1) ('pod','data','model') mesh for the "
                         "two-level wire")
    ap.add_argument("--optimizer", choices=("sgd", "momentum", "adamw"),
                    default="sgd")
    ap.add_argument("--sampling", choices=("rr", "rr_once", "rr_shared", "wr"),
                    default="rr")
    ap.add_argument("--clients", type=int, default=None,
                    help="fleet population size C: sample a cohort of "
                         "mesh-rank-many clients per round from C clients "
                         "whose shifts live in a host state store "
                         "(DESIGN.md §3.9); default = full participation")
    ap.add_argument("--cohort-mode", choices=COHORT_MODES, default="rr",
                    help="'rr' = cohort-RR (every client once per fleet "
                         "epoch); 'with_replacement' = i.i.d. baseline")
    ap.add_argument("--buffer-k", type=int, default=None,
                    help="buffered-async trigger: apply the server update "
                         "once K of the cohort's reports arrive "
                         "(DESIGN.md §3.10); default = synchronous rounds")
    ap.add_argument("--late", choices=LATE_POLICIES, default="discount",
                    help="late reports past the K-of-m deadline: "
                         "'discount' folds them in with weight "
                         "discount/(1+staleness); 'drop' discards them and "
                         "rewinds their RR data cursor (exactly-once)")
    ap.add_argument("--discount", type=float, default=0.5,
                    help="staleness-discount numerator for --late discount")
    ap.add_argument("--chaos-dropout", type=float, default=0.0,
                    help="P(a cohort client goes dark for the round) — "
                         "deterministic per (--chaos-seed, round)")
    ap.add_argument("--chaos-straggler", type=float, default=0.0,
                    help="P(an alive client reports after the deadline)")
    ap.add_argument("--chaos-delay", type=float, default=1.0,
                    help="mean extra straggler latency (base-round units)")
    ap.add_argument("--chaos-store-fail", type=float, default=0.0,
                    help="P(a store gather/scatter raises a transient "
                         "error); the driver retries with backoff")
    ap.add_argument("--chaos-retries", type=int, default=3,
                    help="bounded retry budget per store op")
    ap.add_argument("--chaos-backoff", type=float, default=0.0,
                    help="base seconds for exponential retry backoff")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed every fault draw derives from")
    ap.add_argument("--data-store", default=None,
                    help="page the fleet population's DATASETS from disk: "
                         "lay them out as per-client rows in sharded memmap "
                         "files under this directory (built on first run, "
                         "reused if present) and stream each cohort through "
                         "the deterministic lookahead pager — host RSS is "
                         "bounded by the lookahead window, batches are "
                         "bit-identical to the in-RAM path (DESIGN.md §3.11)")
    ap.add_argument("--store-path", default=None,
                    help="back the fleet client-state store with np.memmap "
                         "shards under this directory (zero pages cost "
                         "nothing on disk); default keeps shards in host "
                         "RAM — large --clients runs want this")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--checkpoint", default=None, help="save state here at end")
    ap.add_argument("--resume", default=None,
                    help="checkpoint to restore (state + data-stream cursor; "
                         "the continued run bit-matches an uninterrupted one)")
    ap.add_argument("--no-prefetch", dest="prefetch", action="store_false",
                    help="disable the double-buffered host prefetch")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--telemetry", default=None, metavar="JSONL",
                    help="stream structured run events (round metrics, host "
                         "phase spans, wire/chaos/pager counters) to this "
                         "JSONL file; inspect with `python -m "
                         "repro.telemetry` (DESIGN.md §3.14). Off by "
                         "default and byte-identical when off")
    ap.add_argument("--trace", default=None, metavar="JSON",
                    help="also export a Chrome/Perfetto trace_event JSON at "
                         "exit (implies --telemetry to a sibling file when "
                         "not set)")
    ap.add_argument("--device-metrics", action="store_true",
                    help="carry opt-in compression diagnostics in the "
                         "step's metrics pytree (‖ḡ−D‖², shift norms) — "
                         "changes the compiled step, so off by default")
    return ap


def telemetry_path(args) -> str | None:
    """--telemetry wins; --trace alone derives a sibling JSONL path."""
    if args.telemetry:
        return args.telemetry
    if args.trace:
        base = (args.trace[:-5] if args.trace.endswith(".json")
                else args.trace)
        return base + ".telemetry.jsonl"
    return None


class Trainer(NamedTuple):
    """Everything `run` needs, built once from the CLI arguments."""

    cfg: ArchConfig
    mesh: Any
    agg: CompressedAggregation
    m: int
    n_batches: int
    remat: Any
    jitted: Any
    abstract: steps.TrainState
    shardings: steps.TrainState
    batch_sh: Any


def build(ap: argparse.ArgumentParser, args) -> Trainer:
    """Mesh, config, wire and compiled-step builder for one run."""
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg, remat, label = get_config(args.arch), "full", "full"
    else:
        try:
            mesh = make_attached_mesh(args.pods)
        except ValueError as e:
            ap.error(f"--pods {args.pods}: {e}")
        if jax.default_backend() == "tpu":
            try:
                cfg = get_chip_config(args.arch)
            except ValueError as e:
                ap.error(str(e))
            remat, label = "full", "chip-share"
        else:
            cfg = reduced(get_config(args.arch), seq=args.seq)
            remat, label = False, "reduced"
    m = num_clients(mesh)
    n_batches = 8
    slotted = args.agg == "diana_rr"
    if slotted and args.sampling != "rr_shared":
        ap.error("--agg diana_rr needs --sampling rr_shared: the per-slot "
                 "wire reads/writes one shared shift-table row per round, "
                 "so every client must walk its data in the same index "
                 "order (DESIGN.md §3.8)")
    if args.clients is not None:
        if args.clients < m:
            ap.error(f"--clients {args.clients} < mesh client ranks {m}: "
                     "the cohort fills every mesh rank each round")
        if slotted and (args.cohort_mode != "rr" or args.clients % m != 0):
            ap.error("--agg diana_rr on the fleet path needs --cohort-mode "
                     "rr and --clients divisible by the mesh client count "
                     "(shared-slot wire contract, DESIGN.md §3.9)")
        if fleet_is_async(args) and args.local_steps > 1:
            ap.error("--buffer-k/--chaos-* need --local-steps 1: a NASTYA "
                     "epoch has no well-defined RR rewind point for a "
                     "mid-epoch straggler (DESIGN.md §3.10)")
    elif fleet_is_async(args):
        ap.error("--buffer-k/--late drop/--chaos-* are fleet knobs — pass "
                 "--clients C to run partial participation")
    # cohort-sampled fleets rescale the DIANA mean-shift update by M/C so
    # the server's resident mean shift tracks the population mean h_bar
    # rather than a (C/M)-inflated cohort estimate (DESIGN.md §3.10);
    # M == C gives 1.0, the exact full-participation form
    mean_scale = m / args.clients if args.clients is not None else 1.0
    try:
        agg = CompressedAggregation(method=args.agg, wire=args.wire,
                                    fraction=args.fraction,
                                    n_slots=n_batches if slotted else 1,
                                    mean_scale=mean_scale,
                                    shift_dtype=jnp.float32,
                                    wire_dtype=args.wire_dtype,
                                    wire_levels=args.wire_levels)
    except ValueError as e:
        ap.error(str(e))
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=args.lr, eta=args.eta,
        local_steps=args.local_steps, remat=remat,
        optimizer=args.optimizer, elastic=fleet_is_async(args),
        debug_metrics=args.device_metrics)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract.params))
    print(f"config={cfg.name}/{label} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"remat={remat}")
    print(f"arch={cfg.name} ({n_params/1e6:.1f}M params) clients={m} "
          f"agg={args.agg}/{args.wire}"
          + (f"/{args.wire_dtype}" if args.wire_dtype != "f32" else "")
          + (f" levels={args.wire_levels}" if args.wire_levels else "")
          + f" k/d={args.fraction} "
          f"local_steps={args.local_steps} opt={args.optimizer}"
          + (f" fleet=C{args.clients}/{args.cohort_mode}"
             if args.clients is not None else ""))
    return Trainer(cfg, mesh, agg, m, n_batches, remat, jitted, abstract,
                   shardings, batch_sh)


def init_state(args, tr: Trainer) -> steps.TrainState:
    """Fresh state, built by one jitted program straight into the step's
    shardings — no array is ever whole on one device first."""
    init = jax.jit(
        lambda key: steps.init_train_state(
            key, tr.cfg, tr.agg, tr.m, optimizer=args.optimizer,
            mesh=tr.mesh, local_steps=args.local_steps),
        out_shardings=tr.shardings)
    return init(salts.root_key(0, salts.PARAMS_KEY_SALT))


def record_wire_paths(agg, params) -> None:
    """Counters `wire.window_leaves` and `wire.dense_leaves`, one of each
    per wire level (tag `level`, the leaves' element count as tag
    `elements`): which path of the exchange each level runs
    (`CompressedAggregation.wire_paths`). Host side, once per run."""
    for level, paths in agg.wire_paths(params).items():
        for path in ("window", "dense"):
            telemetry.counter(f"wire.{path}_leaves", paths[f"{path}_leaves"],
                              level=level,
                              elements=paths[f"{path}_elements"])


def record_moe_rows(t: int, metrics: dict) -> None:
    """Counters `moe.local_rows` (copies routed to the experts this chip
    holds, summed over the layers and clients), `moe.max_expert_rows`
    (the most any held expert got in a layer) and `moe.expert_chunks`
    (the chunks those copies ran in, summed over the layers and clients:
    one a layer when the expert layer's block covers them) of round `t`;
    MoE configurations only. The values stay on the device until the
    sink's writer reads them."""
    if "moe_local_rows" in metrics:
        telemetry.counter("moe.local_rows", metrics["moe_local_rows"],
                          round=t)
        telemetry.counter("moe.max_expert_rows",
                          metrics["moe_max_expert_rows"], round=t)
        telemetry.counter("moe.expert_chunks",
                          metrics["moe_expert_chunks"], round=t)


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    enable_compile_cache()
    tr = build(ap, args)
    tpath = telemetry_path(args)
    if tpath is not None:
        telemetry.install(telemetry.MetricsSink(tpath))
        flags = {k: v for k, v in sorted(vars(args).items())
                 if isinstance(v, (str, int, float, bool, type(None)))}
        agg_c = steps.configure_agg(tr.agg, tr.mesh, args.local_steps)
        wire = agg_c.wire_bytes_per_round(tr.abstract.params)
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(tr.abstract.params))
        telemetry.run_meta({
            "argv": flags, "arch": tr.cfg.name, "n_params": n_params,
            "mesh_clients": tr.m,
            "wire_bytes_per_round": {k: int(v) for k, v in wire.items()}})
        record_wire_paths(agg_c, tr.abstract.params)
    try:
        return run(args, tr)
    finally:
        sink = telemetry.active()
        if sink is not None:
            telemetry.uninstall()
            sink.close()
            print(f"telemetry -> {tpath}")
            if args.trace:
                n = telemetry.write_trace(
                    telemetry.read_events(tpath), args.trace)
                print(f"trace -> {args.trace} ({n} trace events)")


def run(args, tr: Trainer, callback=None) -> steps.TrainState:
    """The training loop (full participation, or the fleet with
    --clients); returns the final state. `callback(t, state, metrics)`
    runs after each step or round."""
    cfg, mesh, agg, m, n_batches = tr.cfg, tr.mesh, tr.agg, tr.m, tr.n_batches
    jitted, abstract, shardings, batch_sh = (tr.jitted, tr.abstract,
                                             tr.shardings, tr.batch_sh)
    slotted = args.agg == "diana_rr"
    b = max(1, args.batch // m)
    if args.clients is not None:
        return run_fleet(args, tr, b, callback)
    data = {"tokens": synthetic_token_batches(
        vocab=cfg.vocab, seq_len=args.seq, batch=b,
        num_batches=n_batches, num_clients=m, seed=0)}
    sampler = ReshuffleSampler(m, n_batches, mode=args.sampling, seed=1)

    start_step = 0
    if args.resume:
        meta = load_meta(args.resume)
        cursor = (meta.get("meta") or {}).get("data_stream")
        if cursor is None:
            raise SystemExit(f"{args.resume}: no data-stream cursor in "
                             "manifest — not a train.py checkpoint?")
        if cursor["sampler"] != sampler.spec() or \
                cursor["local_steps"] != args.local_steps:
            raise SystemExit(
                f"{args.resume}: checkpointed stream {cursor} does not match "
                "this run's sampler/local_steps — refusing to resume onto a "
                "different data stream")
        start_step = cursor["train_step"]

    with jax.set_mesh(mesh):
        if args.resume:
            state = restore_train_state(args.resume, abstract, shardings)
            print(f"resumed {args.resume} at step {start_step} "
                  f"(epoch {cursor['epoch']}, batch {cursor['step']})")
        else:
            state = init_state(args, tr)
        key = salts.root_key(0, salts.ROUNDS_KEY_SALT)

        if telemetry.enabled():
            agg_c = steps.configure_agg(agg, mesh, args.local_steps)
            wire = agg_c.wire_bytes_per_round(abstract.params)
            bits_per_client = 8.0 * (wire["intra_pod"] if agg_c.client_axes
                                     else wire["inter_pod"])
        reporter = telemetry.ConsoleReporter(
            unit="step", log_every=args.log_every, total=args.steps,
            start=start_step)

        # the NASTYA-aware stream owns RR order, client-major assembly,
        # modality alignment, and prefetch+device_put overlap
        stream = make_batch_stream(
            data, sampler, local_steps=args.local_steps,
            extras=stub_modalities(cfg, m, n_batches, b),
            put=lambda batch: jax.device_put(batch, batch_sh(batch)),
            prefetch=args.prefetch, start_step=start_step)
        with stream:
            # start the rate clock AFTER restore + stream construction so
            # neither checkpoint-restore nor first-build time folds in
            reporter.start()
            for t, batch in zip(range(start_step, args.steps), stream):
                if slotted:
                    # the shared slot stream is a pure function of the
                    # stateless sampler, so --resume re-derives it exactly
                    slots = jnp.asarray(shared_slots_for_step(
                        sampler, t, args.local_steps, n_slots=agg.n_slots))
                    with telemetry.span("step_dispatch", round=t):
                        state, metrics = jitted(state, batch, key, slots)
                else:
                    with telemetry.span("step_dispatch", round=t):
                        state, metrics = jitted(state, batch, key)
                if telemetry.enabled():
                    telemetry.counter("wire.uplink_bits",
                                      m * bits_per_client, round=t)
                    telemetry.round_metrics(t, metrics)
                    record_moe_rows(t, metrics)
                reporter.report(t, metrics)
                if callback is not None:
                    callback(t, state, metrics)
            if args.checkpoint:
                save_pytree(args.checkpoint, jax.device_get(state),
                            step=int(state.step),
                            meta={"data_stream": stream.cursor_meta()})
                print(f"checkpoint -> {args.checkpoint} "
                      f"(cursor {stream.cursor})")
    return state


if __name__ == "__main__":
    main()
