"""Build one cell from its files and run it: set-up, warm rounds, the
measured window, the reference comparison.

A cell is a configuration file (`configs/<config>.json`) under a traffic
mix (`traffic/<traffic>.json`), with the limits of its comparison in
`limits/<workload>.json`; the configuration file names its reference
model, `references/<reference>.py` (`reference_model`). The trainer is
assembled as the program's own `launch/train.py::build` assembles it: the
mesh of the attached chips,
`CompressedAggregation` with float32 shifts, and `steps.make_train_step`
with full remat. Full participation is fed by `data.pipeline`'s batch
stream, the fleet by `fleet.FleetRunner` over a host `ClientStateStore`
and a `CohortSampler`. Weights, tokens and the round key come from the
run's seed, made by this directory's own `weights.py` and `tokens.py`.

Set-up drives the compiled step from the seed through its first three
rounds, the same call and feed as the window, and reads what the
comparison needs from its state. The window then measures whole rounds,
each ending in `block_until_ready`, until `seconds` have passed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import compare
import reference
import scopes
import tokens
import weights

WARM_ROUNDS = 3  # rounds the reference follows; at least one whole round
N_BATCHES = 8  # batches per client in the RR data set, as train.py keeps
WEIGHT_SALT, ROUND_SALT, TOKEN_SALT = 1, 2, 3
FAULTS = ("unchanged", "half_batch")
REFERENCES = Path(__file__).resolve().parent / "references"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def reference_model(conf: dict):
    """The reference model module a configuration names: `"reference":
    "<name>"` loads `references/<name>.py`, which exports
    `param_shapes(m)`, `loss(params, tokens, m, fp8=False)` and
    `flops_per_token(m, seq)`. There is no default."""
    name = conf.get("reference")
    if name is None:
        raise ValueError(f"{conf['name']}: the configuration names no "
                         "reference model (key 'reference')")
    path = REFERENCES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"{conf['name']}: reference model {name!r} does "
                         f"not exist (no {path})")
    spec = importlib.util.spec_from_file_location(f"reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file; every size the
    file states must be what the program runs."""
    from repro.configs import get_chip_config, get_config

    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["overrides"])
    for key, want in conf["model"].items():
        got = getattr(cfg, key)
        if key == "dtype":
            got = jnp.dtype(got).name
        if got != want:
            raise ValueError(f"{conf['name']}: {key} runs as {got!r}, the "
                             f"file states {want!r}")
    if conf.get("chip_share") and cfg != get_chip_config(conf["arch"]):
        raise ValueError(f"{conf['name']}: differs from the program's "
                         f"chip-share config of {conf['arch']}")
    return cfg


def build(cfg, traffic: dict, mesh):
    from repro.core.dist import CompressedAggregation
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    m = num_clients(mesh)
    fleet = traffic["participation"] == "fleet"
    population = traffic["population"] if fleet else m
    if traffic["local_steps"] != 1:
        raise ValueError("the comparison follows one local step per round")
    agg = CompressedAggregation(
        method=traffic["method"], wire=traffic["wire"],
        fraction=traffic["fraction"], mean_scale=m / population,
        shift_dtype=jnp.float32, wire_dtype=traffic["wire_dtype"],
        wire_levels=traffic["wire_levels"])
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=traffic["lr"],
        local_steps=traffic["local_steps"], remat="full")
    return SimpleNamespace(
        cfg=cfg, mesh=mesh, agg=agg, m=m, fleet=fleet, population=population,
        jitted=jitted, abstract=abstract, shardings=shardings,
        batch_sh=batch_sh, alpha=agg.shift_lr,
        beta=agg.shift_lr * m / population)


class Feed:
    """The step as the window calls it, keeping the first rounds' rows
    for the reference; `fault` breaks it underneath for the tests."""

    def __init__(self, jitted, keep: int, m: int, b: int,
                 fault: str | None = None):
        if fault not in (None,) + FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.jitted, self.keep, self.m, self.b = jitted, keep, m, b
        self.fault = fault
        self.rows = []

    def __call__(self, state, batch, key, *rest):
        if len(self.rows) < self.keep:
            self.rows.append(batch["tokens"])
        if self.fault == "half_batch":
            t = batch["tokens"]
            x = t.reshape(self.m, self.b, -1)[:, :self.b // 2]
            batch = {**batch, "tokens": jnp.concatenate([x, x], 1).reshape(
                t.shape)}
        if self.fault == "unchanged":
            kept = jax.tree.map(jnp.copy, state)
            return kept, self.jitted(state, batch, key, *rest)[1]
        return self.jitted(state, batch, key, *rest)


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def client_leaf_norms(tree):
    """(clients, leaves) norms of a client-stacked tree."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                       axis=tuple(range(1, x.ndim))))
                      for x in jax.tree.leaves(tree)], axis=1)


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def _window_full(step, state, stream, key, seconds):
    times = []
    with annotate("bench_window"):
        start = time.perf_counter()
        while True:
            with annotate("round"):
                t0 = time.perf_counter()
                with annotate("next_batch"):
                    batch = next(stream)
                with annotate("dispatch"):
                    state, metrics = step(state, batch, key)
                with annotate("wait"):
                    jax.block_until_ready((state, metrics))
                t1 = time.perf_counter()
            times.append(t1 - t0)
            if t1 - start >= seconds:
                break
    return state, metrics, times, t1 - start


def _window_fleet(runner, state, key, rounds):
    """`rounds` fleet rounds in one call of the runner, as the program's
    own loop runs them; each round is timed from the previous round's
    end (its scatter has waited for the device) to its own."""
    times, last = [], {}

    def keep(r, s, metrics):
        last["loss"] = metrics["loss"]
        now = time.perf_counter()
        times.append(now - last.get("t", start))
        last["t"] = now

    with annotate("bench_window"):
        start = time.perf_counter()
        with annotate("fleet_round"):
            state = runner.run(state, key, rounds, callback=keep)
        with annotate("wait"):
            jax.block_until_ready(state)
    return state, last, times, last["t"] - start


def _warm_fleet(runner, store, state, key, prog, beta):
    """The first rounds in one call of the runner, as the program's own
    loop runs them, reading after each round the shift row it wrote back
    and, after the first, the mean shift. Returns the round times with
    the final state appended."""
    times, start = [], [time.perf_counter()]
    before = [store.cursor.copy()]

    def read(r, state, metrics):
        prog["loss"].append(float(metrics["loss"]))
        cohort = np.flatnonzero(store.cursor != before[0])
        before[0] = store.cursor.copy()
        rows = jax.tree.leaves(store.gather(cohort))
        prog["shift"] += [[float(np.linalg.norm(x[j])) for x in rows]
                          for j in range(cohort.size)]
        if "grad" not in prog:
            prog["grad"] = (np.asarray(leaf_norms(state.mean_shift))
                            / beta).tolist()
        now = time.perf_counter()
        times.append(now - start[0])
        start[0] = now

    state = runner.run(state, key, WARM_ROUNDS, callback=read)
    return times + [state]


def run(conf: dict, traffic: dict, limits: dict, *, seed: int,
        seconds: float, devices, t_start: float, trace_dir=None,
        fault: str | None = None) -> dict:
    """One run of a cell; returns the record the result line is made of."""
    from jax.sharding import Mesh

    from repro import telemetry
    from repro.data.pipeline import make_batch_stream
    from repro.data.reshuffle import ReshuffleSampler
    from repro.fleet import ClientStateStore, CohortSampler, FleetRunner
    from repro.launch import steps

    model = reference_model(conf)
    cfg = arch_config(conf)
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"))
    tr = build(cfg, traffic, mesh)
    m, b, seq = tr.m, traffic["seqs_per_client"], traffic["seq"]
    clients = tr.population
    wkey = weights.seed_key(seed, WEIGHT_SALT)
    rkey = weights.seed_key(seed, ROUND_SALT)
    data = {"tokens": tokens.token_batches(
        vocab=cfg.vocab, seq_len=seq, batch=b, num_batches=N_BATCHES,
        num_clients=clients, seed=[seed, TOKEN_SALT])}
    # the fleet's store owns every client's shift: the state holds none
    # until the runner puts the cohort's rows in
    drop = {"shifts": None} if tr.fleet else {}
    init = jax.jit(lambda k: steps.init_train_state(
        k, cfg, tr.agg, m, mesh=mesh)._replace(
            params=weights.make_params(k, tr.abstract.params), **drop),
        out_shardings=tr.shardings._replace(**drop))
    prog = {"loss": [], "shift": []}
    sink = None
    feed = Feed(tr.jitted, WARM_ROUNDS, m, b, fault)
    with jax.set_mesh(mesh):
        state = init(wkey)
        if tr.fleet:
            sampler = ReshuffleSampler(clients, N_BATCHES, mode="rr",
                                       seed=seed)
            store = ClientStateStore.create(
                tr.abstract.params, clients, tr.agg.rule,
                n_slots=tr.agg.n_slots, dtype=tr.agg.shift_dtype)
            runner = FleetRunner(
                feed, tr.abstract, tr.shardings, tr.batch_sh, agg=tr.agg,
                mesh=mesh, data=data, sampler=sampler,
                cohorts=CohortSampler(clients, m, mode=traffic["cohort_mode"],
                                       seed=seed),
                store=store, local_steps=1, prefetch=True)
            closer = runner
        else:
            stream = make_batch_stream(
                data, ReshuffleSampler(m, N_BATCHES, mode="rr", seed=seed),
                put=lambda x: jax.device_put(x, tr.batch_sh(x)),
                prefetch=True)
            closer = stream
        with closer:
            if tr.fleet:
                warm_times = _warm_fleet(runner, store, state, rkey, prog,
                                         tr.beta)
                # the store holds every row: the state enters the window as
                # it entered the warm rounds, without the last cohort's (a
                # caller that kept them would hold two rows in a round)
                state = warm_times.pop()._replace(shifts=None)
            else:
                for t in range(WARM_ROUNDS):
                    state, metrics = feed(state, next(stream), rkey)
                    prog["loss"].append(float(metrics["loss"]))
                    if t == 0:
                        prog["shift"] = np.asarray(
                            client_leaf_norms(state.shifts)).tolist()
                        prog["grad"] = (np.asarray(leaf_norms(
                            state.mean_shift)) / tr.beta).tolist()
            prog["change"] = np.asarray(weights.init_distance(
                wkey, state.params)).tolist()
            feeds = [np.asarray(r).reshape(m, b, seq + 1) for r in feed.rows]
            op_scopes = phase_map_s = None
            if trace_dir is not None:
                t_map = time.perf_counter()
                op_scopes = scopes.op_scopes(
                    scopes.step_hlo(tr, feed.rows[-1], rkey))
                phase_map_s = time.perf_counter() - t_map
                sink = telemetry.install(telemetry.MetricsSink())
                jax.profiler.start_trace(str(trace_dir))
            window_start = time.perf_counter()
            if tr.fleet:
                rounds = max(1, math.ceil(seconds / warm_times[-1]))
                state, metrics, times, window_s = _window_fleet(
                    runner, state, rkey, rounds)
            else:
                state, metrics, times, window_s = _window_full(
                    tr.jitted, state, stream, rkey, seconds)
            loss_end = float(metrics["loss"])
            if trace_dir is not None:
                jax.profiler.stop_trace()
        peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
                   if d.memory_stats() else 0 for d in devices)
    spans = {}
    if sink is not None:
        telemetry.uninstall()
        for ev in sink.events():
            if ev.get("kind") == "span":
                spans.setdefault(ev["name"], []).append(ev["dur"])
        sink.close()
    rounds = len(times)
    record = {
        "setup_s": window_start - t_start, "window_s": window_s,
        "rounds": rounds, "round_s": times,
        "tokens": rounds * m * b * seq, "chips": len(devices),
        "memory_peak_bytes": int(peak), "spans": spans,
        "op_scopes": op_scopes, "phase_map_s": phase_map_s,
        "flops_per_token": model.flops_per_token(conf["model"], seq),
        "finite": bool(np.isfinite(loss_end)),
    }
    del state, feed
    if tr.fleet:
        del runner, store
    else:
        del stream
    gc.collect()
    ref = reference.run(
        model, conf["model"], wkey, rkey, feeds,
        fraction=traffic["fraction"], lr=traffic["lr"], alpha=tr.alpha,
        beta=tr.beta, fresh_clients=tr.fleet)
    record["gaps"] = compare.gaps(prog, ref)
    record["correct"], record["checks"] = compare.judge(record["gaps"],
                                                        limits)
    record["readings"] = {"program": prog, "reference": ref}
    record["feeds"] = feeds
    record["keys"] = (wkey, rkey)
    record["wire"] = {"fraction": traffic["fraction"], "lr": traffic["lr"],
                      "alpha": tr.alpha, "beta": tr.beta,
                      "fresh_clients": tr.fleet}
    return record
