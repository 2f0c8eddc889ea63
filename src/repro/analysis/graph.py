"""Layer-2 jaxpr census: trace the real train steps, audit the wire
(DESIGN.md §3.12).

The AST linter can't see what a program compiles TO. This layer traces
`launch.steps.make_train_step` for every wire method on a flat and a 2-pod
mesh — `jit(...).trace` / `.lower` only, no device execution — and checks
the compiled artifact against the repo's analytic claims:

collective census
    Inside the fully-manual shard_map wire regions only EXPLICIT collectives
    exist (GSPMD inserts its comms later, invisibly to the jaxpr), so the
    collective equations ARE the wire. Per level (axis names distinguish the
    intra-pod exchange over "data" from the inter-pod one over "pod") the
    f32/bf16 wire must show exactly L psums — one per parameter leaf — and
    their payload bytes must equal
    `CompressedAggregation.wire_bytes_per_round` exactly. The packed wires
    (wire_dtype 'packed8'/'packed4', DESIGN.md §3.13) have NO psums on the
    wire axes: the census must instead show exactly 2L all_gathers per level
    (the byte slab + the f32 scale sideband, per leaf) whose per-rank
    operand bytes sum to the same analytic number — all_gather payload is
    what each rank CONTRIBUTES (the operand), matching the accounting. The
    CLI runs TP=1 meshes ((4,1) and (2,2,1)): per-device jaxpr payloads
    divide the lane (cols) dimension by the model-axis size, while the
    analytic model counts a client's full contribution, so byte EQUALITY
    holds only at TP=1 (the f32-lane caveat: on TP>1 meshes compare counts,
    or scale by the model-axis factor — tests/test_analysis.py does the
    former).

dtype audit
    No float64 anywhere in the traced program (a silent x64 promotion would
    double every wire payload), and the output state's leaf dtypes must
    equal the input state's (a promotion inside the step would break
    donation silently before it broke numerics).

donation audit
    The step donates its input state (`donate_argnums=(0,)`); every state
    leaf must actually alias an output buffer in the lowered StableHLO
    (`tf.aliasing_output`). A dtype/shape mismatch makes XLA silently drop
    the alias and double peak memory.

elastic invariant
    The elastic step's participation-weights vector must be a live runtime
    input of the jaxpr — consumed by the program, never constant-folded —
    which is the single-compile guarantee: cohorts can shrink/grow without
    retracing.

Everything here must be importable only AFTER XLA_FLAGS forces >= 8 host
devices (the CLI driver does this; tests inherit conftest's env).
"""
from __future__ import annotations

import numpy as np

from repro.analysis.findings import Finding

RULES = {
    "census-collective-count":
        "collective count per wire level != the wire model (one psum per "
        "leaf; two all_gathers per leaf on packed wires)",
    "census-collective-bytes":
        "collective payload bytes != the analytic wire_bytes_per_round",
    "census-unexpected-collective":
        "a collective over axes no wire level owns (e.g. 'model'), or of a "
        "kind the wire_dtype must not emit (psum on a packed wire)",
    "census-dtype-promotion":
        "float64 in the traced step, or state dtype changed in flight",
    "census-donation":
        "a donated state buffer is not aliased in the lowered program",
    "census-elastic-invariant":
        "the elastic weights vector is not a live jaxpr input",
    "census-telemetry-identity":
        "installing a telemetry sink changed the traced step's jaxpr — "
        "instrumentation leaked into the compiled program",
}

# Census points: every wire method on both topologies. TP=1 so payload
# bytes match the analytic model exactly (see module docstring).
CENSUS_METHODS = ("q", "diana", "diana_rr", "ef")
CENSUS_MESHES = (
    ("flat", (4, 1), ("data", "model")),
    ("two_pod", (2, 2, 1), ("pod", "data", "model")),
)
# Non-f32 transports audited on top: packed8 on both topologies (the
# all-gather wire replaces every psum), packed4 + bf16 spot-checked flat.
CENSUS_PACKED_METHODS = ("q", "diana_rr")
CENSUS_EXTRA_DTYPES = ("packed4", "bf16")


def _iter_jaxprs(jaxpr):
    """The jaxpr and every sub-jaxpr nested in its equation params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for vv in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(vv, "jaxpr", vv)
                if hasattr(inner, "eqns"):
                    yield from _iter_jaxprs(inner)


def collective_census(jaxpr, primitive: str = "psum"
                      ) -> dict[tuple[str, ...], tuple[int, int]]:
    """{axes -> (eqn count, payload bytes)} for one collective primitive
    over all nested jaxprs. Payload is the per-rank OPERAND bytes — for
    psum the reduced buffer, for all_gather what this rank contributes
    (the gathered result is axis_size times larger but only the operand
    crosses the wire once per rank)."""
    out: dict[tuple[str, ...], tuple[int, int]] = {}
    for jx in _iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name != primitive:
                continue
            axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            axes = (axes,) if isinstance(axes, str) else tuple(axes)
            nbytes = sum(
                int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                for v in eqn.invars)
            c, b = out.get(axes, (0, 0))
            out[axes] = (c + 1, b + nbytes)
    return out


def has_float64(jaxpr) -> bool:
    for jx in _iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "dtype", None) is not None:
                    if str(aval.dtype) == "float64":
                        return True
    return False


def _trace_step(cfg, mesh, method: str, *, elastic: bool = False,
                fraction: float = 0.25, wire_dtype: str = "f32"):
    """Build + trace one train step; returns everything the checks need."""
    import jax
    import jax.numpy as jnp

    from repro.core.dist import CompressedAggregation
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    agg0 = CompressedAggregation(method=method, wire="shared",
                                 fraction=fraction,
                                 shift_dtype=jnp.float32,
                                 wire_dtype=wire_dtype)
    jitted, abstract, _, _ = steps.make_train_step(
        cfg, mesh, agg=agg0, remat=False, seq_shard=False, elastic=elastic)
    agg = steps.configure_agg(agg0, mesh, 1)
    m = num_clients(mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((2 * m, cfg.max_seq + 1),
                                            jnp.int32)}
    # round-key argument: abstract typed-key scalar (eval_shape never
    # materializes a key, so this is not a root-key construction site)
    key = jax.ShapeDtypeStruct((), jax.eval_shape(jax.random.key, 0).dtype)
    extra = []
    if agg.rule.slotted:
        extra.append(jax.ShapeDtypeStruct((1,), jnp.int32))
    if elastic:
        extra.append(jax.ShapeDtypeStruct((m,), jnp.float32))
    with jax.set_mesh(mesh):
        traced = jitted.trace(abstract, batch, key, *extra)
        lowered = jitted.lower(abstract, batch, key, *extra)
    return traced, lowered, abstract, agg


def check_step(cfg, mesh, method: str, label: str, *,
               wire_dtype: str = "f32") -> list[Finding]:
    """All census checks for one (mesh, method, wire_dtype) point."""
    import jax

    traced, lowered, abstract, agg = _trace_step(cfg, mesh, method,
                                                 wire_dtype=wire_dtype)
    where = f"jaxpr:{label}/{method}"
    if wire_dtype != "f32":
        where += f"/{wire_dtype}"
    out: list[Finding] = []
    jaxpr = traced.jaxpr.jaxpr

    packed = wire_dtype in ("packed8", "packed4")
    wire_prim = "all_gather" if packed else "psum"
    levels = collective_census(jaxpr, wire_prim)
    wire = agg.wire_bytes_per_round(abstract.params)
    n_leaves = len(jax.tree.leaves(abstract.params))
    # packed wires move two gathers per leaf: the byte slab + the f32
    # per-row scale sideband; psum wires one reduction per leaf
    per_leaf = 2 if packed else 1
    expected = {}
    if agg.client_axes:
        expected[tuple(agg.client_axes)] = wire["intra_pod"]
    if agg.pod_axes and agg.pod_size > 1:
        expected[tuple(agg.pod_axes)] = wire["inter_pod"]

    for axes, (count, nbytes) in sorted(levels.items()):
        if axes not in expected:
            out.append(Finding(
                file=where, line=0, rule="census-unexpected-collective",
                message=f"{wire_prim} over axes {axes} — no wire level owns "
                        "these axes (GSPMD comms never appear in the jaxpr, "
                        "so this is an explicit stray collective)"))
            continue
        if count != per_leaf * n_leaves:
            out.append(Finding(
                file=where, line=0, rule="census-collective-count",
                message=f"{count} {wire_prim}s over {axes}, expected "
                        f"{per_leaf * n_leaves} ({per_leaf} per parameter "
                        "leaf)"))
        if nbytes != expected[axes]:
            out.append(Finding(
                file=where, line=0, rule="census-collective-bytes",
                message=f"{wire_prim} payload over {axes} is {nbytes} "
                        f"B/rank, analytic wire model says {expected[axes]} "
                        "B — the wire and its accounting have diverged"))
    for axes in expected:
        if axes not in levels:
            out.append(Finding(
                file=where, line=0, rule="census-collective-count",
                message=f"no {wire_prim}s over {axes} — an expected wire "
                        "level is missing from the compiled step"))
    # the OTHER wire primitive must not appear at all: a psum on a packed
    # wire would sum per-rank byte lattices with different scales (wrong);
    # an all_gather on a psum wire is an unaccounted dense collective
    other = "psum" if packed else "all_gather"
    for axes, (count, _) in sorted(collective_census(jaxpr, other).items()):
        out.append(Finding(
            file=where, line=0, rule="census-unexpected-collective",
            message=f"{count} {other}(s) over {axes} — the {wire_dtype} "
                    f"wire must move only {wire_prim}s"))

    if has_float64(jaxpr):
        out.append(Finding(
            file=where, line=0, rule="census-dtype-promotion",
            message="float64 appears in the traced step — a silent x64 "
                    "promotion doubles wire payloads"))
    in_dtypes = [str(x.dtype) for x in jax.tree.leaves(abstract)]
    out_state = traced.out_info[0]
    out_dtypes = [str(x.dtype) for x in jax.tree.leaves(out_state)]
    if in_dtypes != out_dtypes:
        out.append(Finding(
            file=where, line=0, rule="census-dtype-promotion",
            message="output state dtypes differ from the input state — "
                    "an in-flight promotion breaks donation silently"))

    n_state = len(jax.tree.leaves(abstract))
    aliased = lowered.as_text().count("tf.aliasing_output")
    if aliased != n_state:
        out.append(Finding(
            file=where, line=0, rule="census-donation",
            message=f"{aliased} of {n_state} donated state buffers alias an "
                    "output — XLA silently dropped the rest (shape/dtype "
                    "mismatch), doubling peak memory"))
    return out


def check_elastic(cfg, mesh, label: str, method: str = "diana"
                  ) -> list[Finding]:
    """The elastic step's weights must be live runtime data in the jaxpr."""
    traced, _, _, _ = _trace_step(cfg, mesh, method, elastic=True)
    where = f"jaxpr:{label}/{method}+elastic"
    jaxpr = traced.jaxpr.jaxpr
    wvar = jaxpr.invars[-1]  # weights is the trailing argument
    used = any(wvar in eqn.invars for eqn in jaxpr.eqns)
    if not used:
        return [Finding(
            file=where, line=0, rule="census-elastic-invariant",
            message="the (m,) participation-weights input is never consumed "
                    "— it was constant-folded, so cohort changes would "
                    "retrace (the single-compile guarantee is broken)")]
    return []


def check_telemetry_identity(cfg, mesh, label: str, method: str = "diana"
                             ) -> list[Finding]:
    """The zero-cost-when-off claim, compiled form: tracing the step with
    an active in-memory `MetricsSink` must yield a byte-identical jaxpr —
    telemetry lives entirely on the host side of the jit boundary."""
    from repro import telemetry

    traced_off, _, _, _ = _trace_step(cfg, mesh, method)
    sink = telemetry.install(telemetry.MetricsSink())
    try:
        traced_on, _, _, _ = _trace_step(cfg, mesh, method)
    finally:
        telemetry.uninstall()
        sink.close()
    where = f"jaxpr:{label}/{method}+telemetry"
    if str(traced_off.jaxpr) != str(traced_on.jaxpr):
        return [Finding(
            file=where, line=0, rule="census-telemetry-identity",
            message="the traced step's jaxpr differs with a telemetry sink "
                    "installed — something threads host instrumentation "
                    "through the compiled program")]
    return []


def run_census() -> list[Finding]:
    """The CLI entry point: every method on both topologies + elastic."""
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_test_mesh

    cfg = reduced(get_config("stablelm-1.6b"), seq=16)
    findings: list[Finding] = []
    for label, shape, axes in CENSUS_MESHES:
        mesh = make_test_mesh(shape, axes)
        for method in CENSUS_METHODS:
            findings.extend(check_step(cfg, mesh, method, label))
        for method in CENSUS_PACKED_METHODS:
            findings.extend(check_step(cfg, mesh, method, label,
                                       wire_dtype="packed8"))
    flat_mesh = make_test_mesh(*CENSUS_MESHES[0][1:])
    for wire_dtype in CENSUS_EXTRA_DTYPES:
        findings.extend(check_step(cfg, flat_mesh, "diana",
                                   CENSUS_MESHES[0][0],
                                   wire_dtype=wire_dtype))
    findings.extend(check_elastic(cfg, flat_mesh, CENSUS_MESHES[0][0]))
    findings.extend(check_telemetry_identity(cfg, flat_mesh,
                                             CENSUS_MESHES[0][0]))
    return sorted(findings)
