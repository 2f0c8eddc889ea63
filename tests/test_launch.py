"""Launch layer on the 8-device test mesh: sharding rules, train/serve steps.

The full 512-device dry-run lives in launch/dryrun.py (own process, own
XLA_FLAGS); here the same step builders run on a 4x2 (data x model) mesh
with reduced configs — every code path that the production mesh exercises,
at unit-test cost.
"""
import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.core.dist import CompressedAggregation
from repro.launch import sharding, steps
from repro.launch.mesh import make_test_mesh, num_clients
from repro.models import transformer as T

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices")



def _subprocess_isolated(test_fn):
    """Run the decorated test in its own pytest subprocess.

    XLA:CPU's collective runtime aborts natively when several distinct
    multi-device executables execute in one process (every test below passes
    in isolation); process isolation is the documented workaround. The
    512-device dry-run COMPILES all programs in one process — only host
    EXECUTION trips this.
    """
    import functools
    import os
    import subprocess
    import sys

    @functools.wraps(test_fn)
    def wrapper(*args, **kwargs):
        if os.environ.get("REPRO_SUBTEST") == "1":
            return test_fn(*args, **kwargs)
        request = kwargs.pop("request", None)
        node = f"tests/test_launch.py::{test_fn.__name__}"
        if args or kwargs:
            params = "-".join(str(v) for v in list(args) + list(kwargs.values()))
            node += f"[{params}]"
        env = dict(os.environ, REPRO_SUBTEST="1",
                   PYTHONPATH=os.environ.get("PYTHONPATH", "src"))
        r = subprocess.run([sys.executable, "-m", "pytest", "-q", "-x", node],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-1000:]

    return wrapper

S, B = 16, 8


def make_batch(cfg, key):
    batch = {"tokens": jax.random.randint(key, (B, S + 1), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.vision_patches, cfg.d_model), cfg.dtype)
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    return batch


def test_param_specs_shapes():
    cfg = reduced(get_config("deepseek-67b"))
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    specs = sharding.param_specs(params)
    blocks = specs["blocks"]
    assert blocks["mixer"]["wq"] == P(None, None, "model")
    assert blocks["mixer"]["wo"] == P(None, "model", None)
    assert blocks["ffn"]["w_down"] == P(None, "model", None)
    assert specs["embed"] == P("model", None)
    assert blocks["ln1"]["scale"] == P(None, None)


def test_moe_specs():
    cfg = reduced(get_config("dbrx-132b"))
    params = jax.eval_shape(lambda: T.init_params(jax.random.key(0), cfg))
    specs = sharding.param_specs(params)
    assert specs["blocks"]["ffn"]["w_up"] == P(None, None, None, "model")
    assert specs["blocks"]["ffn"]["w_down"] == P(None, None, "model", None)
    assert specs["blocks"]["ffn"]["router"] == P(None, None, None)


# Execution coverage runs the paper's wire (method="diana"); the dense
# (uncompressed pmean) wire EXECUTES into a native XLA:CPU abort on this
# jaxlib (the program compiles — including at 512 dry-run devices — and the
# math is covered by test_dist's manual-mesh aggregation tests). Dense stays
# compile-covered via launch/dryrun.py --agg dense.
@pytest.mark.parametrize("arch,method", [
    ("stablelm-1.6b", "diana"), ("qwen2-moe-a2.7b", "diana"),
    ("rwkv6-7b", "diana"), ("hymba-1.5b", "diana"),
])
@_subprocess_isolated
def test_train_step_runs_sharded(arch, method):
    """Compressed train step on the 4x2 mesh: runs, loss finite + params
    move."""
    cfg = reduced(get_config(arch), seq=S)
    mesh = make_test_mesh((4, 2), ("data", "model"))
    agg = CompressedAggregation(method=method, wire="shared", fraction=0.25,
                                shift_dtype=jnp.float32)
    # seq_shard=False: XLA:CPU's collective runtime aborts on the
    # resharding-heavy seq-parallel program when several multi-device
    # executables run in one process; the seq-parallel path is exercised by
    # the dry-run (compile) and by test_train_step_loss_decreases (single
    # executable per process).
    jitted, abstract, shardings, _ = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.05, remat=False, seq_shard=False)
    with jax.set_mesh(mesh):
        state = steps.init_train_state(jax.random.key(0), cfg, agg,
                                       num_clients(mesh))
        state = jax.device_put(state, shardings)
        batch = make_batch(cfg, jax.random.key(1))
        key = jax.random.key(2)
        # the step donates its input state — snapshot params first
        before = [np.asarray(x, np.float32)
                  for x in jax.tree.leaves(state.params)]
        new_state, metrics = jitted(state, batch, key)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert int(new_state.step) == 1
        # params moved
        delta = sum(
            float(np.sum(np.abs(np.asarray(a, np.float32) - b)))
            for a, b in zip(jax.tree.leaves(new_state.params), before))
        assert delta > 0


@_subprocess_isolated
def test_train_step_loss_decreases():
    cfg = reduced(get_config("stablelm-1.6b"), seq=S)
    mesh = make_test_mesh((4, 2), ("data", "model"))
    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.5,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, _ = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.2, remat=False)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg,
                                   num_clients(mesh)), shardings)
        batch = make_batch(cfg, jax.random.key(1))
        losses = []
        for t in range(30):
            state, metrics = jitted(state, batch, jax.random.key(3))
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.05, losses[::10]


@pytest.mark.parametrize("arch", ["starcoder2-15b", "whisper-medium"])
@_subprocess_isolated
def test_serve_step_sharded(arch):
    cfg = reduced(get_config(arch), seq=S)
    mesh = make_test_mesh((4, 2), ("data", "model"))
    params = T.init_params(jax.random.key(0), cfg)
    cache = T.init_cache(params, cfg, batch=B, cache_len=S)
    serve, lower_args = steps.make_serve_step(cfg, mesh)
    tokens = jnp.zeros((B, 1), jnp.int32)
    with jax.set_mesh(mesh):
        jitted, (psh, csh, tsh) = lower_args(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache),
            jax.ShapeDtypeStruct(tokens.shape, tokens.dtype),
        )
        params = jax.device_put(params, psh)
        cache = jax.device_put(cache, csh)
        tokens = jax.device_put(tokens, tsh)
        logits, new_cache = jitted(params, cache, tokens, jnp.int32(0))
        assert logits.shape == (B, 1, cfg.padded_vocab())
        assert bool(jnp.all(jnp.isfinite(logits)))


def test_train_docstring_example_flags_stay_valid():
    """Doc/flag drift guard: the module docstring's example command must
    parse through the real argparse surface, and the --fraction default
    must equal the value the docstring advertises (the paper's k/d)."""
    import re

    from repro.launch import train

    m = re.search(r"python -m repro\.launch\.train (.+?)\n\n", train.__doc__,
                  re.S)
    assert m, "train.py docstring lost its example command line"
    example = m.group(1).replace("\\\n", " ").replace(
        "[--production-mesh]", "")
    parser = train.build_parser()
    args = parser.parse_args(example.split())
    assert args.fraction == parser.get_default("fraction") == 0.02
    assert "--fraction 0.02" in train.__doc__


def test_attached_mesh_puts_one_client_on_each_device():
    from repro.launch.mesh import make_attached_mesh

    n = jax.device_count()
    mesh = make_attached_mesh()
    assert mesh.axis_names == ("data", "model")
    assert (mesh.shape["data"], mesh.shape["model"]) == (n, 1)
    assert num_clients(mesh) == n
    pods = make_attached_mesh(2)
    assert pods.axis_names == ("pod", "data", "model")
    assert tuple(pods.shape.values()) == (2, n // 2, 1)
    with pytest.raises(ValueError, match="do not split"):
        make_attached_mesh(3)


def test_build_runs_reduced_config_off_the_chip():
    """Off a TPU, train.build runs the reduced config without remat on the
    attached devices (the chip-share cut is for the chip only)."""
    from repro.launch import train

    ap = train.build_parser()
    tr = train.build(ap, ap.parse_args(["--seq", "16", "--clients", "16"]))
    assert tr.cfg == reduced(get_config("stablelm-1.6b"), seq=16)
    assert tr.remat is False
    assert tr.m == jax.device_count()
    assert tr.agg.mean_scale == tr.m / 16


def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache goes to the fixed .jax_cache/ at the checkout root."""
    import pathlib

    from repro.launch import cache

    root = pathlib.Path(__file__).resolve().parents[1]
    assert cache.CHECKOUT_CACHE == root / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cache.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# the shared-wire DIANA update is window-sparse (core/dist.py `_level`)
# ---------------------------------------------------------------------------

# elementwise primitives whose full-leaf f32 output would be a dense pass
_ELEMENTWISE = {"add", "sub", "mul", "div", "neg", "max", "min", "select_n",
                "convert_element_type", "integer_pow", "square"}


def _trace_wire(method: str, wire: str):
    """(wire shard_map body jaxprs, configured agg, params) of the tiny
    StableLM train step on a (4, 1) mesh, traced only."""
    from repro.analysis.graph import _iter_jaxprs

    cfg = reduced(get_config("stablelm-1.6b"), seq=S)
    mesh = make_test_mesh((4, 1), ("data", "model"))
    agg0 = CompressedAggregation(method=method, wire=wire, fraction=0.25,
                                 n_slots=2 if method == "diana_rr" else 1,
                                 shift_dtype=jnp.float32)
    jitted, abstract, _, _ = steps.make_train_step(
        cfg, mesh, agg=agg0, remat=False, seq_shard=False)
    batch = {"tokens": jax.ShapeDtypeStruct((8, S + 1), jnp.int32)}
    key = jax.ShapeDtypeStruct((), jax.eval_shape(jax.random.key, 0).dtype)
    extra = ([jax.ShapeDtypeStruct((1,), jnp.int32)]
             if method == "diana_rr" else [])
    with jax.set_mesh(mesh):
        jaxpr = jitted.trace(abstract, batch, key, *extra).jaxpr.jaxpr
    bodies = [list(_iter_jaxprs(getattr(e.params["jaxpr"], "jaxpr",
                                        e.params["jaxpr"])))
              for e in jaxpr.eqns if e.primitive.name == "shard_map"
              and "wire" in str(e.source_info.name_stack)]
    assert bodies, "no wire region in the traced step"
    return ([jx for b in bodies for jx in b],
            steps.configure_agg(agg0, mesh), abstract.params)


def _wire_census(method: str, wire: str):
    """(jitted kernel names, full-leaf f32 elementwise ops, wire_paths) of
    the wire region. A leaf counts as full where its window is smaller
    than the leaf; its shape and row view are the dense shapes."""
    jaxprs, agg, params = _trace_wire(method, wire)
    dense_shapes = set()
    for leaf in jax.tree.leaves(params):
        rows = int(np.prod(leaf.shape[:-1])) if leaf.ndim >= 2 \
            else leaf.shape[0]
        nb = -(-rows // 8)
        if max(1, int(agg.fraction * nb)) < nb:
            dense_shapes |= {tuple(leaf.shape),
                             (rows, leaf.shape[-1] if leaf.ndim >= 2 else 1)}
    names, dense_ops = set(), []
    for jx in jaxprs:
        for e in jx.eqns:
            if e.primitive.name in ("jit", "pjit"):
                names.add(e.params["name"])
            if e.primitive.name in _ELEMENTWISE:
                dense_ops += [e.primitive.name for v in e.outvars
                              if v.aval.dtype == jnp.float32
                              and tuple(v.aval.shape) in dense_shapes]
    return names, dense_ops, agg.wire_paths(params)


@pytest.mark.parametrize("method", ["diana", "diana_rr"])
def test_shared_diana_wire_is_window_sparse(method):
    """No fused DIANA kernel, no dense scatter and no full-leaf f32
    elementwise pass in the wire: the window is gathered, exchanged and
    written back in place (the direction's one dense pass casts to bf16),
    and the accounting puts every leaf on the window path."""
    names, dense_ops, paths = _wire_census(method, "shared")
    assert "randk_decompress_into" in names
    assert not names & {"diana_shift_update", "randk_compress",
                        "randk_decompress"}, names
    assert dense_ops == [], dense_ops
    (level,) = paths.values()
    assert level["dense_leaves"] == level["dense_elements"] == 0
    assert level["window_leaves"] == 15


@pytest.mark.parametrize("method,wire,kernel,f32_passes", [
    ("ef", "shared", "randk_decompress", True),
    ("q", "shared", "randk_decompress", False),
    ("diana", "independent", "diana_shift_update", True),
])
def test_dense_wires_keep_their_dense_path(method, wire, kernel, f32_passes):
    """'ef' (dense residual), 'q' and the independent wire keep the dense
    path: the dense kernels, full-leaf f32 payloads where the rule keeps
    memory ('q' moves its leaf-dtype message), and the accounting puts
    every leaf there."""
    names, dense_ops, paths = _wire_census(method, wire)
    assert kernel in names, names
    assert "randk_decompress_into" not in names
    assert bool(dense_ops) == f32_passes, dense_ops
    (level,) = paths.values()
    assert level["window_leaves"] == level["window_elements"] == 0
    assert level["dense_leaves"] == 15


def test_wire_path_counters():
    """train.py records the accounting once per run, host side: one
    `wire.window_leaves` and one `wire.dense_leaves` counter per level."""
    from repro import telemetry
    from repro.launch import train

    params = {"a": jnp.zeros((16, 8)), "b": jnp.zeros((5,))}
    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    for method, window in (("diana", 2), ("ef", 0)):
        agg = steps.configure_agg(
            CompressedAggregation(method=method, wire="shared"), mesh)
        sink = telemetry.install(telemetry.MetricsSink())
        try:
            train.record_wire_paths(agg, params)
        finally:
            telemetry.uninstall()
        events = [e for e in sink.events() if e["kind"] == "counter"]
        sink.close()
        assert telemetry.validate_events(events) == []
        got = {(e["name"], e["tags"]["level"]): (e["value"],
                                                 e["tags"]["elements"])
               for e in events}
        for level in ("intra_pod", "inter_pod"):
            assert got[("wire.window_leaves", level)] == (
                window, 133 if window else 0)
            assert got[("wire.dense_leaves", level)] == (
                2 - window, 0 if window else 133)


def test_moe_row_counters():
    """train.py records a MoE step's routing counts each round: the held
    experts' copies, the most one expert got, and the chunks the copies
    ran in; a dense step's metrics record none."""
    from repro import telemetry
    from repro.launch import train

    metrics = {"moe_local_rows": jnp.int32(70626),
               "moe_max_expert_rows": jnp.int32(4337),
               "moe_expert_chunks": jnp.int32(5)}
    sink = telemetry.install(telemetry.MetricsSink())
    try:
        train.record_moe_rows(3, metrics)
        train.record_moe_rows(4, {"loss": jnp.float32(1.0)})
    finally:
        telemetry.uninstall()
    events = [e for e in sink.events() if e["kind"] == "counter"]
    sink.close()
    assert telemetry.validate_events(events) == []
    got = {e["name"]: (e["value"], e["round"]) for e in events}
    assert got == {"moe.local_rows": (70626, 3),
                   "moe.max_expert_rows": (4337, 3),
                   "moe.expert_chunks": (5, 3)}
