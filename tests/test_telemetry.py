"""repro.telemetry — structured metrics, spans, and the zero-cost-when-off
event pipeline (DESIGN.md §3.14).

Covers the JSONL schema round-trip (torn-tail tolerance mirroring the
checkpoint reader), span nesting + Chrome trace export, the acceptance
criterion that a run with an active sink is BIT-IDENTICAL to one without
(params, shift tables, bits) for diana and diana_rr, the unified
sync/async participation schema (`completed`/`on_time`/`weight_sum`), the
chaos counters pinned against the deterministic planner schedule, and the
opt-in device-side compression diagnostics.
"""
import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.data.pipeline import make_batch_stream
from repro.data.reshuffle import ReshuffleSampler
from repro.fleet import (AsyncFleetRunner, AsyncPlanner, ChaosConfig,
                         CohortSampler, ClientStateStore, FleetRunner)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices"
)


# ---------------------------------------------------------------------------
# events: JSONL round-trip, torn tail, validation
# ---------------------------------------------------------------------------

def _emit_mix(sink):
    sink.run_meta({"arch": "tiny", "n_params": 7})
    with sink.span("outer", round=0):
        with sink.span("inner"):
            pass
    sink.counter("fleet.uplink_bits", np.float64(96.0), round=0)
    sink.counter("fleet.staleness_hist", [1, 0, 2])
    sink.round_metrics(0, {"loss": np.float32(1.5),
                           "grad_norm": jnp.float32(2.0),
                           "completed": 4})


def test_jsonl_round_trip_and_validation(tmp_path):
    """read_events is the inverse of the sink's writes, values land as
    plain JSON scalars (jax/np materialized on the writer thread), and
    every record passes schema validation."""
    path = str(tmp_path / "run.telemetry.jsonl")
    with telemetry.MetricsSink(path) as sink:
        _emit_mix(sink)
    events = telemetry.read_events(path)
    assert [e["kind"] for e in events] == [
        "run_meta", "span", "span", "counter", "counter", "round_metrics"]
    assert telemetry.validate_events(events) == []
    # spans record on EXIT, so inner lands first, one depth level down
    inner, outer = events[1], events[2]
    assert (inner["name"], inner["depth"]) == ("inner", 1)
    assert (outer["name"], outer["depth"]) == ("outer", 0)
    assert outer["dur"] >= inner["dur"] >= 0
    rm = events[5]
    assert rm["round"] == 0
    assert rm["metrics"]["loss"] == pytest.approx(1.5)
    assert isinstance(rm["metrics"]["loss"], float)  # materialized
    assert events[3]["value"] == pytest.approx(96.0)
    assert events[4]["value"] == [1, 0, 2]


def test_torn_tail_tolerated_interior_corruption_raises(tmp_path):
    """Like the checkpoint reader: a torn FINAL line (the crash case the
    buffered writer can leave) is dropped silently; damage anywhere else
    is out-of-band corruption and raises."""
    path = str(tmp_path / "run.telemetry.jsonl")
    with telemetry.MetricsSink(path) as sink:
        _emit_mix(sink)
    n = len(telemetry.read_events(path))
    with open(path, "a") as f:
        f.write('{"v": 1, "kind": "coun')  # torn mid-record
    assert len(telemetry.read_events(path)) == n
    lines = open(path).read().splitlines()
    lines[2] = lines[2][:10]
    bad = str(tmp_path / "corrupt.jsonl")
    with open(bad, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(telemetry.TelemetryError):
        telemetry.read_events(bad)


def test_validate_flags_bad_records():
    assert telemetry.validate_events([{"v": 99, "kind": "span"}])
    assert telemetry.validate_events([{"v": 1, "kind": "nope", "ts": 0}])
    assert telemetry.validate_events(
        [{"v": 1, "kind": "counter", "ts": 0, "name": "x",
          "value": "not-a-number"}])
    assert telemetry.validate_events(
        [{"v": 1, "kind": "span", "ts": 0.0, "dur": -1.0, "name": "s",
          "tid": 1, "depth": 0}])


def test_module_helpers_are_noops_when_off():
    assert not telemetry.enabled()
    with telemetry.span("anything", round=3):
        pass
    telemetry.counter("x", 1)
    telemetry.round_metrics(0, {"loss": 1.0})
    telemetry.run_meta({})
    assert telemetry.active() is None


def test_span_without_a_sink_is_the_shared_noop():
    from repro.telemetry import sink as sink_mod

    assert telemetry.active() is None
    assert telemetry.span("input_wait") is sink_mod._NOOP
    assert telemetry.span("gather", round=3) is sink_mod._NOOP


def test_import_is_numpy_only():
    """`import repro.telemetry` and installing a sink pull in no jax; the
    first span entered imports the profiler for its host annotation."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro import telemetry\n"
        "sink = telemetry.install(telemetry.MetricsSink())\n"
        "assert 'jax' not in sys.modules, 'jax imported before a span'\n"
        "with telemetry.span('x'):\n"
        "    pass\n"
        "assert 'jax.profiler' in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_spans_land_in_the_profiler_host_plane(tmp_path):
    """A span of an installed sink is a profiler host event of the same
    bare name (its args stay in the record): the batch stream's
    `input_wait` on the caller's thread and `assemble` on the worker's."""
    from jax.profiler import ProfileData

    data = {"x": np.arange(2 * 4 * 3, dtype=np.float32).reshape(2, 4, 3)}
    sink = telemetry.install(telemetry.MetricsSink())
    try:
        with jax.profiler.trace(str(tmp_path)):
            with telemetry.span("phase", round=7):
                with make_batch_stream(data, ReshuffleSampler(2, 4, seed=0),
                                       prefetch=True) as stream:
                    for _ in range(3):
                        next(stream)
    finally:
        telemetry.uninstall()
        sink.close()
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host"):
            for i, line in enumerate(plane.lines):  # a line per thread
                for e in line.events:
                    host.setdefault(e.name, set()).add((plane.name, i))
    assert {"phase", "input_wait", "assemble"} <= set(host)
    assert host["input_wait"] == host["phase"]
    assert host["input_wait"].isdisjoint(host["assemble"])
    recorded = [e for e in sink.events() if e["name"] == "phase"]
    assert recorded[0]["args"] == {"round": 7}


def test_session_installs_and_always_uninstalls():
    sink = telemetry.MetricsSink()
    with pytest.raises(RuntimeError, match="boom"):
        with telemetry.session(sink):
            assert telemetry.active() is sink
            raise RuntimeError("boom")
    assert telemetry.active() is None


def test_spans_from_worker_threads_get_their_own_tid_and_depth():
    with telemetry.MetricsSink() as sink:
        def worker():
            with sink.span("worker_phase"):
                pass

        with sink.span("main_phase"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        spans = {e["name"]: e for e in sink.events()}
    assert spans["worker_phase"]["tid"] != spans["main_phase"]["tid"]
    # nesting depth is per-thread: the worker span is NOT inside main's
    assert spans["worker_phase"]["depth"] == 0
    assert spans["main_phase"]["depth"] == 0


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------

def test_trace_export_golden(tmp_path):
    """Chrome trace_event shape: leading process metadata, spans as
    complete "X" events in microseconds, numeric counters and round
    metrics as "C" tracks, run_meta as a global instant."""
    with telemetry.MetricsSink() as sink:
        _emit_mix(sink)
        events = sink.events()
    trace = telemetry.to_trace_events(events)
    assert trace[0] == {"ph": "M", "name": "process_name", "pid": 1,
                        "ts": 0, "args": {"name": "repro.telemetry"}}
    by_ph = {}
    for ev in trace[1:]:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert {e["name"] for e in by_ph["X"]} == {"outer", "inner"}
    for ev in by_ph["X"]:
        src = next(e for e in events if e.get("name") == ev["name"])
        assert ev["ts"] == pytest.approx(src["ts"] * 1e6)
        assert ev["dur"] == pytest.approx(src["dur"] * 1e6)
        assert ev["tid"] == src["tid"]
    # the list-valued staleness hist has no counter track; the scalar does
    c_names = {e["name"] for e in by_ph["C"]}
    assert c_names == {"fleet.uplink_bits", "metrics/loss",
                       "metrics/grad_norm", "metrics/completed"}
    assert len(by_ph["i"]) == 1

    out = str(tmp_path / "trace.json")
    n = telemetry.write_trace(events, out)
    loaded = json.load(open(out))
    assert loaded["displayTimeUnit"] == "ms"
    assert len(loaded["traceEvents"]) == n == len(trace)


def test_cli_validate_summary_trace(tmp_path, capsys):
    from repro.telemetry.__main__ import main as tmain

    path = str(tmp_path / "run.telemetry.jsonl")
    with telemetry.MetricsSink(path) as sink:
        _emit_mix(sink)
    out = str(tmp_path / "t.json")
    assert tmain([path, "--validate", "--summary", "--to-trace", out]) == 0
    text = capsys.readouterr().out
    assert "schema OK" in text and "span" in text
    assert json.load(open(out))["traceEvents"]
    # schema problems exit 1
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"v": 1, "kind": "span", "ts": 0}\n')
    assert tmain([bad, "--validate"]) == 1
    # unreadable exits 2
    assert tmain([str(tmp_path / "missing.jsonl"), "--validate"]) == 2


# ---------------------------------------------------------------------------
# the acceptance criterion: telemetry-on bit-matches telemetry-off
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("stablelm-1.6b"), seq=8)
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _fleet_setup(mesh, method, *, n=3, elastic=False):
    from repro.core.dist import CompressedAggregation
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    cfg = _tiny_cfg()
    m = num_clients(mesh)
    slotted = method == "diana_rr"
    agg = CompressedAggregation(method=method, wire="shared", fraction=0.5,
                                n_slots=n if slotted else 1,
                                shift_dtype=jnp.float32,
                                mean_scale=m / (2 * m))
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.05, remat=False, seq_shard=False,
        elastic=elastic)
    return cfg, m, agg, jitted, abstract, shardings, batch_sh


def _population_tokens(cfg, C, n, b, seq, seed=0):
    from repro.data.tokens import synthetic_token_batches

    return {"tokens": np.asarray(synthetic_token_batches(
        vocab=cfg.vocab, seq_len=seq, batch=b, num_batches=n,
        num_clients=C, seed=seed))}


def _run_fleet(mesh, method, setup, data, *, total, sink=None):
    """One C = 2m cohort-RR fleet walk; returns (final state, store,
    callback metrics) — with `sink` installed for the duration."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    cfg, m, agg, jitted, abstract, shardings, batch_sh = setup
    C = 2 * m
    mode = "rr_shared" if method == "diana_rr" else "rr"
    seen = []
    if sink is not None:
        telemetry.install(sink)
    try:
        with jax.set_mesh(mesh):
            state = jax.device_put(
                steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                       mesh=mesh), shardings)
            store = ClientStateStore.create(
                abstract.params, C, WIRE_RULES[method], n_slots=agg.n_slots,
                dtype=np.float32, shard_size=3)
            with FleetRunner(
                    jitted, abstract, shardings, batch_sh, agg=agg,
                    mesh=mesh, data=data,
                    sampler=ReshuffleSampler(C, 3, mode=mode, seed=1),
                    cohorts=CohortSampler(C, m, seed=9),
                    store=store) as runner:
                state = runner.run(
                    state, jax.random.key(4), total,
                    callback=lambda t, s, mt: seen.append((t, mt)))
            return jax.device_get(state), store, seen
    finally:
        if sink is not None:
            telemetry.uninstall()


@needs_mesh
@pytest.mark.parametrize("method", ["diana", "diana_rr"])
def test_telemetry_on_bit_matches_off(method, mesh_4x2):
    """THE §3.14 acceptance criterion, host side: a fleet run with an
    active sink walks a byte-identical trajectory — params, store shift
    tables, bit counters — and the sink sees every phase span (including
    assemble from the prefetch worker's own thread) plus one round_metrics
    per round with the unified participation schema."""
    mesh = mesh_4x2
    setup = _fleet_setup(mesh, method)
    cfg, m = setup[0], setup[1]
    data = _population_tokens(cfg, 2 * m, 3, 1, 8)
    total = 3

    off_state, off_store, off_seen = _run_fleet(
        mesh, method, setup, data, total=total)
    sink = telemetry.MetricsSink()
    on_state, on_store, on_seen = _run_fleet(
        mesh, method, setup, data, total=total, sink=sink)

    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(off_state.params),
            jax.tree_util.tree_leaves_with_path(on_state.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), pa
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(
                off_store.gather(np.arange(2 * m))),
            jax.tree_util.tree_leaves_with_path(
                on_store.gather(np.arange(2 * m)))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), pa
    assert np.array_equal(off_store.bits, on_store.bits)
    assert np.array_equal(off_store.cursor, on_store.cursor)

    events = sink.events()
    sink.close()
    assert telemetry.validate_events(events) == []
    spans = [e for e in events if e["kind"] == "span"]
    names = {e["name"] for e in spans}
    assert {"gather", "step_dispatch", "shift_fetch", "scatter",
            "assemble"} <= names
    # the device wait and the host store write are separate spans, one of
    # each per round
    for name in ("shift_fetch", "scatter"):
        assert sum(e["name"] == name for e in spans) == total
    # prefetch assembly runs on the worker thread, phases on the caller's
    tids = {e["name"]: e["tid"] for e in spans}
    assert tids["assemble"] != tids["step_dispatch"]
    rms = [e for e in events if e["kind"] == "round_metrics"]
    assert [e["round"] for e in rms] == list(range(total))
    # one static run_meta with the wire accounting
    (meta,) = [e for e in events if e["kind"] == "run_meta"]
    assert meta["meta"]["bits_per_client_round"] > 0
    assert set(meta["meta"]["wire_bytes_per_round"]) == {
        "intra_pod", "inter_pod", "dense"}
    # the sync path emits the SAME participation schema as async
    # (satellite: one schema across drivers)
    for (t, mt) in on_seen:
        assert mt["completed"] == mt["on_time"] == m
        assert mt["weight_sum"] == float(m)
    assert [mt for _, mt in off_seen][0].keys() == \
        [mt for _, mt in on_seen][0].keys()


# ---------------------------------------------------------------------------
# chaos counters pinned against the deterministic planner schedule
# ---------------------------------------------------------------------------

@needs_mesh
def test_async_chaos_counters_match_planner_replay(mesh_4x2):
    """Every chaos counter the async driver emits must equal the closed-
    form replay of its deterministic `AsyncPlanner`/`FaultyStore` schedule
    — and `weight_sum` must recover the RAW pre-normalization buffered
    mass (1 per on-time reporter + the staleness discounts), not the
    vacuous post-rescale sum (always m)."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    method, total = "diana", 6
    setup = _fleet_setup(mesh, method, elastic=True)
    cfg, m, agg, jitted, abstract, shardings, batch_sh = setup
    C = 2 * m
    data = _population_tokens(cfg, C, 3, 1, 8)
    chaos = ChaosConfig(dropout=0.25, straggler=0.4, delay=1.0,
                        store_fail=0.15, max_retries=6, seed=5)
    discount = 0.5

    sink = telemetry.MetricsSink()
    telemetry.install(sink)
    seen = []
    try:
        with jax.set_mesh(mesh):
            state = jax.device_put(
                steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                       mesh=mesh), shardings)
            store = ClientStateStore.create(
                abstract.params, C, WIRE_RULES[method], n_slots=1,
                dtype=np.float32, shard_size=3)
            with AsyncFleetRunner(
                    jitted, abstract, shardings, batch_sh, agg=agg,
                    mesh=mesh, data=data,
                    sampler=ReshuffleSampler(C, 3, seed=1),
                    cohorts=CohortSampler(C, m, seed=9), store=store,
                    buffer_k=2, discount=discount, chaos=chaos) as runner:
                runner.run(state, jax.random.key(4), total,
                           callback=lambda t, s, mt: seen.append(mt))
                injected = runner._store.injected_failures
                bits_per_client = runner.checkpoint_meta()[
                    "bits_per_client_round"]
    finally:
        telemetry.uninstall()
    events = sink.events()
    sink.close()
    assert telemetry.validate_events(events) == []

    def totals(name):
        return [e["value"] for e in events
                if e["kind"] == "counter" and e["name"] == name]

    # replay the planner: a pure function of (chaos seed, round)
    planner = AsyncPlanner(m, buffer_k=2, discount=discount, chaos=chaos)
    cohorts = CohortSampler(C, m, seed=9)
    exp_on, exp_late, exp_drop, exp_bits, exp_mass = [], [], [], [], []
    for r in range(total):
        plan = planner(r, cohorts.cohort_for_round(r))
        late = plan.reported & ~plan.on_time
        exp_on.append(int(plan.on_time.sum()))
        exp_late.append(int(late.sum()))
        exp_drop.append(int(m - plan.reported.sum()))
        exp_bits.append(int(plan.reported.sum()) * bits_per_client)
        exp_mass.append(float(plan.on_time.sum()) + float(np.sum(
            discount / (1.0 + plan.latency[late] - plan.deadline))))
    assert totals("fleet.on_time") == exp_on
    assert totals("fleet.late") == exp_late
    assert totals("fleet.dropped") == exp_drop
    assert totals("fleet.uplink_bits") == pytest.approx(exp_bits)
    assert totals("fleet.store_retry") == [1] * injected
    assert injected > 0, "chaos config never fired — test is vacuous"
    for hist, late_n in zip(totals("fleet.staleness_hist"), exp_late):
        assert sum(hist) == late_n
    assert sum(exp_late) > 0, "no late reporters — discount path untested"
    # per-round metrics carry the raw mass, not the normalized sum
    assert len(seen) == total
    for mt, mass, on in zip(seen, exp_mass, exp_on):
        assert mt["weight_sum"] == pytest.approx(mass)
        assert mt["on_time"] == on
        assert "completed" in mt and "deadline" in mt


# ---------------------------------------------------------------------------
# opt-in device-side compression diagnostics
# ---------------------------------------------------------------------------

@needs_mesh
def test_debug_metrics_opt_in(mesh_4x2):
    """debug_metrics=True carries finite ‖ḡ−D‖²/shift-norm scalars in the
    metrics pytree without perturbing the trajectory: params after two
    steps are bitwise identical to the default step's."""
    from repro.core.dist import CompressedAggregation
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    mesh = mesh_4x2
    cfg = _tiny_cfg()
    m = num_clients(mesh)
    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.5,
                                shift_dtype=jnp.float32)

    def run(debug):
        jitted, abstract, shardings, batch_sh = steps.make_train_step(
            cfg, mesh, agg=agg, lr=0.05, remat=False, seq_shard=False,
            debug_metrics=debug)
        data = _population_tokens(cfg, m, 3, 1, 8)
        with jax.set_mesh(mesh):
            state = jax.device_put(
                steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                       mesh=mesh), shardings)
            with make_batch_stream(
                    data, ReshuffleSampler(m, 3, seed=1),
                    put=lambda bt: jax.device_put(bt, batch_sh(bt))) as st:
                for _ in range(2):
                    state, metrics = jitted(state, next(st),
                                            jax.random.key(4))
            return jax.device_get(state), jax.device_get(metrics)

    base_state, base_metrics = run(False)
    dbg_state, dbg_metrics = run(True)
    assert set(base_metrics) == {"loss", "grad_norm"}
    extra = {"compression_err_sq", "direction_norm_sq", "shift_norm_sq",
             "mean_shift_norm_sq"}
    assert set(dbg_metrics) == {"loss", "grad_norm"} | extra
    for k in extra:
        v = float(dbg_metrics[k])
        assert np.isfinite(v) and v >= 0.0, (k, v)
    # compression is lossy here (rand-k at 0.5): the error norm is real
    assert float(dbg_metrics["compression_err_sq"]) > 0.0
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(base_state.params),
            jax.tree_util.tree_leaves_with_path(dbg_state.params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), pa
    assert float(base_metrics["loss"]) == float(dbg_metrics["loss"])


# ---------------------------------------------------------------------------
# console reporter
# ---------------------------------------------------------------------------

def test_console_reporter_cadence_and_skips(capsys):
    rep = telemetry.ConsoleReporter(unit="round", log_every=2, total=5)
    rep.start()
    for t in range(5):
        if t == 3:
            rep.report(t, {"skipped": True})
        else:
            rep.report(t, {"loss": 1.0, "grad_norm": 2.0, "completed": 3},
                       cohort=4)
    lines = capsys.readouterr().out.strip().splitlines()
    # t=0, t=2 (cadence), t=4 (last); t=1 suppressed, t=3 off-cadence
    assert len(lines) == 3
    assert all("done 3/4" in ln for ln in lines)
    assert "round     4" in lines[-1]
    rep2 = telemetry.ConsoleReporter(unit="round", log_every=1, total=4)
    rep2.start()
    rep2.report(0, {"skipped": True})
    assert "skipped" in capsys.readouterr().out
