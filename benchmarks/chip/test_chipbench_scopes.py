"""The step's phases read from inside the program: the HLO scope map, the
idle time inside program spans, and the five readers built on them."""
import importlib.util
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import scopes
import spans
import xtrace
from chipbench_tiny import TINY_LIMITS, tiny_config, tiny_traffic
from test_chipbench_trace import brute_busy, ev, plane

HERE = Path(__file__).resolve().parent

SNIPPET = r"""HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/server_update/mul"}
}

%region_body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %dot.2 = f32[8]{0:T(128)} dot(%gte.1, %gte.1), metadata={op_name="jit(step)/client_grads/vmap(transpose(jvp()))/while/body/checkpoint/rematted_computation/dot_general"}
  %dot.3 = f32[8]{0} dot(%dot.2, %gte.1), metadata={op_name="jit(step)/client_grads/vmap(transpose(jvp()))/while/body/dot_general"}
  %copy.9 = f32[8]{0} copy(%dot.3)
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%gte.1, %copy.9)
}

ENTRY %main.5 (p0: f32[8]) -> f32[] {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="state.params[\'w\']"}
  %copy.6 = f32[8]{0:T(128)} copy(%p0), metadata={op_name="state.params[\'w\']"}
  %fusion.7 = f32[8]{0} fusion(%copy.6), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/client_grads/vmap(jvp())/mul"}
  %tuple.8 = (s32[], f32[8]{0}) tuple(%fusion.7, %fusion.7)
  %while.10 = (s32[], f32[8]{0}) while(%tuple.8), condition=%region_cond, body=%region_body, metadata={op_name="jit(step)/client_grads/vmap(transpose(jvp()))/while"}
  %gte.11 = f32[8]{0} get-tuple-element(%while.10), index=1
  %diana_shift_update.12 = f32[8]{0} custom-call(%gte.11), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/wire/jit(diana_shift_update)/pallas_call"}
  %add.13 = f32[8]{0} add(%diana_shift_update.12, %p0), metadata={op_name="jit(step)/server_update/add"}
  %fold.14 = f32[] reduce(%add.13), to_apply=%region_sum, metadata={op_name="jit(step)/jit(_threefry_fold_in)/add"}
  ROOT %copy.15 = f32[] copy(%fold.14)
}
"""


def test_op_scopes_on_a_fixed_snippet():
    got = scopes.op_scopes(SNIPPET)
    assert got["fusion.7"] == "client_grads/forward"
    assert got["dot.2"] == "client_grads/remat"
    assert got["dot.3"] == "client_grads/backward"
    assert got["diana_shift_update.12"] == "wire"
    assert got["add.13"] == got["multiply.1"] == "server_update"
    assert got["fold.14"] == "other"
    # unnamed or argument-named: the first reader's class, else the first
    # operand's, else the caller's
    assert got["copy.6"] == "client_grads/forward"  # read by fusion.7
    assert got["tuple.8"] == "client_grads/backward"  # read by while.10
    assert got["copy.9"] == "client_grads/backward"  # reads dot.3
    assert got["copy.15"] == "other"  # reads fold.14, an unscoped op
    assert got["gte.1"] == "client_grads/remat"  # read by dot.2 first
    ops = {name: op for _, name, op, _, _ in scopes.instructions(SNIPPET)}
    assert ops["diana_shift_update.12"] == "custom-call"
    assert ops["while.10"] == "while" and ops["copy.6"] == "copy"
    assert ops["tuple.4"] == "tuple" and ops["p0"] == "parameter"
    comps = {name: comp for comp, name, _, _, _ in
             scopes.instructions(SNIPPET)}
    assert comps["p0"] == "ENTRY" and comps["dot.2"] == "region_body"


@pytest.fixture(scope="module")
def tiny_step_hlo():
    """The compiled CPU HLO of the tiny full-participation step."""
    conf, traffic = tiny_config("stablelm-1.6b-chip"), \
        tiny_traffic("diana.full")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    tr = cell.build(cell.arch_config(conf), traffic, mesh)
    rows = jax.ShapeDtypeStruct(
        (tr.m * traffic["seqs_per_client"], traffic["seq"] + 1), jnp.int32,
        sharding=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data", None)))
    with jax.set_mesh(mesh):
        return scopes.step_hlo(tr, rows, jax.random.key(0))


def test_tiny_step_names_its_phases(tiny_step_hlo):
    rows = list(scopes.instructions(tiny_step_hlo))
    got = scopes.op_scopes(tiny_step_hlo)
    # the kernels (interpreted on the CPU) run for the wire
    kernels = [n for _, n, _, op_name, _ in rows
               if any(k in op_name for k in ("diana_shift_update",
                                             "randk_compress",
                                             "randk_decompress"))]
    assert kernels and all(got[n] == "wire" for n in kernels)
    remat = [n for _, n, _, op_name, _ in rows
             if "rematted_computation" in op_name
             and "client_grads" in op_name]
    assert remat and all(got[n] == "client_grads/remat" for n in remat)
    classes = set(got.values())
    assert {"client_grads/forward", "client_grads/backward",
            "server_update"} <= classes
    entry = [n for comp, n, op, _, _ in rows
             if comp == "ENTRY" and op not in ("parameter", "tuple")]
    named = sum(got[n] != scopes.OTHER for n in entry)
    assert named >= 0.9 * len(entry), (named, len(entry))


def test_traced_tiny_run_puts_program_spans_in_the_window(tmp_path):
    """A traced cell run installs a sink, so the profiler's host plane
    carries the program's batch spans inside the window, where
    `spans.host_spans` finds them, and keeps the compiled step's phase map;
    the CPU has no TPU plane to meet the spans with."""
    from jax.profiler import ProfileData

    from repro import telemetry

    rec = cell.run(tiny_config("stablelm-1.6b-chip"),
                   tiny_traffic("diana.full"), TINY_LIMITS,
                   seed=2 ** 33 + 23, seconds=0.0,
                   devices=jax.devices()[:1],
                   t_start=time.perf_counter(), trace_dir=tmp_path)
    assert rec["correct"], rec["checks"]
    assert not telemetry.enabled()
    assert {"input_wait", "assemble"} <= set(rec["spans"])
    assert {"client_grads/remat", "wire", "server_update"} <= set(
        rec["op_scopes"].values())
    planes = list(ProfileData.from_file(
        str(xtrace.find_xplane(tmp_path))).planes)
    (t0, t1), found = spans.host_spans(planes)
    assert {"input_wait", "assemble"} <= set(found)
    assert any(t0 <= s and e <= t1 for s, e in found["input_wait"])
    assert spans.idle_in_span(planes) == []


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_in_span_matches_brute_force(seed):
    rng = np.random.default_rng(seed)
    t0, t1 = 50, 950

    def draw(n, longest):
        return [(int(s), int(s + d)) for s, d in zip(
            rng.integers(0, 1000, n), rng.integers(1, longest, n))]

    ops = draw(30, 60)
    waits, builds = draw(6, 80), draw(6, 80)
    host = plane("/host:CPU",
                 main=[ev("bench_window", t0, t1)]
                 + [ev("input_wait", s, e) for s, e in waits],
                 worker=[ev("assemble", s, e) for s, e in builds])
    chip = plane("/device:TPU:0",
                 XLA_Ops=[ev(f"fusion.{i}", s, e)
                          for i, (s, e) in enumerate(ops)])
    (got,) = spans.idle_in_span([host, chip])
    assert set(got) == {"input_wait", "assemble"}
    for name, intervals in (("input_wait", waits), ("assemble", builds)):
        idle = np.ones(t1 - t0, bool)
        for s, e in ops:
            idle[max(s, t0) - t0:max(min(e, t1) - t0, 0)] = False
        inside = np.zeros(t1 - t0, bool)
        for s, e in intervals:
            inside[max(s, t0) - t0:max(min(e, t1) - t0, 0)] = True
        assert got[name] == pytest.approx(int((idle & inside).sum()) * 1e-9)
    busy = brute_busy(ops, t0, t1)
    reduced = xtrace.reduce([host, chip])
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9)
    assert reduced["chips"][0]["idle_in_span"] == got


def test_idle_in_span_without_program_spans():
    """The recorded v5e trace holds the harness's annotations and none of
    the program's spans: one chip, nothing to report."""
    from jax.profiler import ProfileData

    from test_chipbench_trace import RECORDED

    assert spans.idle_in_span(ProfileData.from_file(str(RECORDED)).planes) \
        == [{}]


def read(name, record, trace):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record, trace)


OP_SCOPES = {"fusion.1": "client_grads/forward",
             "fusion.2": "client_grads/backward",
             "fusion.3": "client_grads/remat",
             "diana_shift_update.4": "wire", "fusion.5": "server_update",
             "fusion.6": "other", "while.7": "client_grads/backward"}
CHIPS = [  # seconds over 2 rounds; the `while` contains fusions 2 and 3
    {"op_s": {"fusion.1": 0.010, "fusion.2": 0.020, "fusion.3": 0.004,
              "diana_shift_update.4": 0.006, "fusion.5": 0.001,
              "fusion.6": 0.0005, "while.7": 0.030},
     "idle_in_span": {"input_wait": 0.0002, "assemble": 0.001}},
    {"op_s": {"fusion.1": 0.011, "fusion.2": 0.020, "fusion.3": 0.002,
              "diana_shift_update.4": 0.008, "fusion.5": 0.001,
              "while.7": 0.030},
     "idle_in_span": {"input_wait": 0.0004}},
]


@pytest.mark.parametrize("name,want", [
    ("grads_device_ms", 17.0),  # chip 0: (10 + 20 + 4) / 2 ms
    ("remat_device_ms", 2.0),
    ("wire_device_ms", 4.0),  # chip 1 is the slower
    ("update_device_ms", 0.5),
    ("input_idle_ms", 0.2),
])
def test_readers_on_a_synthetic_trace(name, want):
    record = {"rounds": 2, "op_scopes": OP_SCOPES}
    assert read(name, record, {"chips": CHIPS}) == pytest.approx(want)


@pytest.mark.parametrize("name", ["grads_device_ms", "remat_device_ms",
                                  "wire_device_ms", "update_device_ms"])
def test_scope_readers_are_silent_without_scopes(name):
    trace = {"chips": CHIPS}
    assert read(name, {"rounds": 2}, trace) is None
    unnamed = {k: "other" for k in OP_SCOPES}
    assert read(name, {"rounds": 2, "op_scopes": unnamed}, trace) is None


def test_input_reader_is_silent_without_the_span():
    chips = [dict(c, idle_in_span={}) for c in CHIPS]
    assert read("input_idle_ms", {"rounds": 2}, {"chips": chips}) is None
    assert read("input_idle_ms", {"rounds": 2},
                {"chips": [{"op_s": {}}]}) is None
