"""Smoke run of the federated trainer's main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one four-chip host

It drives `repro.launch.train` (`build`, `run`) at the chip-share
StableLM-1.6B config: published widths, depth cut to one chip's share,
random weights from a seed, full remat, method `diana` on the shared wire.

One chip: every wire kernel is checked against its `kernels/ref.py` oracle
at the run's leaf shapes; then 5 full-participation steps on the f32 wire,
then 3 fleet rounds (a population of 4 clients whose shifts live in a host
RAM store, one cohort client per round) through `FleetRunner`.

Four chips: 4 clients, 3 steps on the packed8 wire and 3 on the f32 wire
carrying the same 127 levels, then both wires alone on identical inputs.
On the CPU the two are bit-equal. On the chip the f32 wire's all-reduce
adds the ranks in its own order, so the wire alone must agree to within
2^-16 of each leaf's largest entry, and the trained params to within half
the distance they moved from init, with losses within 1e-4.

Only a TPU is accepted, with no fallback. The compression backend is forced
to the Pallas kernels and every compiled step must hold `tpu_custom_call`.
Earlier lines report what ran; the last line is one JSON object. Any failed
check raises, so the exit code is non-zero and no JSON line is printed.
"""
import argparse
import json
import os
import re
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
os.environ["REPRO_COMPRESSION_BACKEND"] = "pallas"

ARCH = "stablelm-1.6b"
SEQ = 2048
SEQS_PER_CLIENT = 2
GiB = 2 ** 30


def device_line(jax):
    d = jax.devices()
    return f"device: {d[0].platform} {d[0].device_kind} x{len(d)}"


def kernel_census(hlo: str) -> Counter:
    """Pallas kernels in a compiled program, by custom-call name."""
    return Counter(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
        hlo))


def memory_line(jax) -> str:
    """The highest peak_bytes_in_use over the attached chips."""
    stats = max((d.memory_stats() or {} for d in jax.devices()),
                key=lambda s: s.get("peak_bytes_in_use", 0))
    peak = stats.get("peak_bytes_in_use", 0)
    return (f"peak_bytes_in_use {peak} ({peak / GiB:.2f} GiB) of "
            f"bytes_limit {stats.get('bytes_limit')}")


def train_argv(m: int, steps: int, *extra: str) -> list[str]:
    return ["--arch", ARCH, "--agg", "diana", "--wire", "shared",
            "--steps", str(steps), "--seq", str(SEQ),
            "--batch", str(SEQS_PER_CLIENT * m), "--log-every", "1", *extra]


def compile_step(jax, tr, args):
    """Compile the train step ahead of the run: compile time, memory plan
    and kernel census. The compiled program goes to the persistent cache,
    where the run's own first call finds it."""
    from repro.core import salts

    sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    state = jax.tree.map(sds, tr.abstract, tr.shardings)
    b = max(1, args.batch // tr.m)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr.m * args.local_steps * b, args.seq + 1), jax.numpy.int32)}
    batch = jax.tree.map(sds, batch, tr.batch_sh(batch))
    key = salts.root_key(0, salts.ROUNDS_KEY_SALT)
    t0 = time.perf_counter()
    with jax.set_mesh(tr.mesh):
        compiled = tr.jitted.lower(state, batch, key).compile()
    secs = time.perf_counter() - t0
    hlo = compiled.as_text()
    kernels = kernel_census(hlo)
    mem = compiled.memory_analysis()
    print(f"compile: {secs:.1f} s; memory plan per chip: arguments "
          f"{mem.argument_size_in_bytes / GiB:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / GiB:.2f} GiB")
    print(f"kernels in the compiled step: {sum(kernels.values())} "
          f"tpu_custom_call {dict(sorted(kernels.items()))}")
    if "tpu_custom_call" not in hlo or not kernels:
        raise RuntimeError("compiled step holds no tpu_custom_call: the wire "
                           "kernels did not lower to Mosaic")


class StepClock:
    """Per-step wall time, each ending in block_until_ready, and the loss."""

    def __init__(self, jax, unit: str):
        self.jax, self.unit = jax, unit
        self.losses, self.times = [], []
        self.t = time.perf_counter()

    def __call__(self, t, state, metrics):
        self.jax.block_until_ready((state, metrics))
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        loss = float(metrics["loss"])
        note = " (includes state init and first dispatch)" if not self.times \
            else ""
        print(f"{self.unit} {t}: {dt * 1e3:.1f} ms, loss {loss:.6f}{note}")
        self.times.append(dt)
        self.losses.append(loss)

    def check(self, n: int):
        import math

        if len(self.losses) != n:
            raise RuntimeError(f"ran {len(self.losses)} {self.unit}s, "
                               f"expected {n}")
        if not all(math.isfinite(x) for x in self.losses):
            raise RuntimeError(f"non-finite loss: {self.losses}")


def train_phase(jax, train, argv: list[str], unit: str, n: int):
    ap = train.build_parser()
    args = ap.parse_args(argv)
    tr = train.build(ap, args)
    from repro.compression.backend import get_backend

    backend = get_backend(tr.agg.backend)
    if backend.name != "pallas":
        raise RuntimeError(f"compression backend is {backend.name!r}")
    compile_step(jax, tr, args)
    clock = StepClock(jax, unit)
    state = train.run(args, tr, callback=clock)
    clock.check(n)
    steady = clock.times[1:]
    if steady:
        print(f"{unit} wall time after the first: "
              f"{min(steady) * 1e3:.1f}..{max(steady) * 1e3:.1f} ms")
    print(memory_line(jax))
    return tr, args, state, clock.losses


def leaf_row_shapes(cfg):
    """(rows, D) row views of the params, rows padded to the wire's block —
    the shapes the wire kernels see for this config."""
    import jax
    import numpy as np

    from repro.compression.backend import BLOCK_ROWS
    from repro.models import transformer

    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.key(0), cfg))
    shapes = set()
    for leaf in jax.tree.leaves(params):
        rows = int(np.prod(leaf.shape[:-1])) if leaf.ndim >= 2 \
            else leaf.shape[0]
        cols = leaf.shape[-1] if leaf.ndim >= 2 else 1
        shapes.add((rows + (-rows) % BLOCK_ROWS, cols))
    return sorted(shapes)


def kernel_parity(jax, cfg, fraction: float, ranks: int):
    """Each wire kernel against its oracle, on the chip, at every leaf
    shape of `cfg`: Rand-block gather, scatter and in-place window
    write-back, the fused DIANA update,
    and the packed wire's pack / unpack / unpack-reduce (levels 127, as the
    packed8 run uses, with `ranks` gathered slabs)."""
    import jax.numpy as jnp

    from repro.compression.backend import BLOCK_ROWS
    from repro.kernels import ref
    from repro.kernels.diana_shift import diana_shift_update
    from repro.kernels.pack import pack_slab, unpack_reduce, unpack_slab
    from repro.kernels.randk import (
        randk_compress,
        randk_decompress,
        randk_decompress_into,
    )

    levels = 127
    f32 = jnp.float32
    worst = Counter()

    def check(name, shape, got, want, atol, max_share=0.0):
        """max |got - want| <= atol everywhere, and at most `max_share` of
        the elements differ at all when max_share > 0."""
        if got.shape != want.shape:
            raise RuntimeError(f"parity {name} {shape}: shapes {got.shape} "
                               f"!= {want.shape}")
        diff = jnp.abs(got.astype(f32) - want.astype(f32))
        err = float(jnp.max(diff))
        share = float(jnp.mean((diff > 0).astype(f32)))
        worst[name] = max(worst[name], err)
        if err > atol or (max_share and share > max_share):
            raise RuntimeError(f"parity {name} {shape}: max |diff| {err} "
                               f"(limit {atol}), {share:g} of elements "
                               "differ")

    for n, d in leaf_row_shapes(cfg):
        keys = jax.random.split(jax.random.key(n * 7919 + d), 8)
        nb = n // BLOCK_ROWS
        kb = max(1, int(fraction * nb))
        rows = jax.random.normal(keys[0], (n, d), f32)
        start = jax.random.randint(keys[1], (), 0, nb)
        vals = randk_compress(rows, start, k_blocks=kb, interpret=False)
        check("randk_compress", (n, d), vals, ref.randk_compress_ref(
            rows, start, k_blocks=kb, block_rows=BLOCK_ROWS), 0.0)
        dense = randk_decompress(vals, start, n_rows=n, interpret=False)
        check("randk_decompress", (n, d), dense, ref.randk_decompress_ref(
            vals, start, n_rows=n, block_rows=BLOCK_ROWS), 0.0)
        del dense
        written = randk_decompress_into(rows, vals, start, jnp.int32(0),
                                        n_rows=n, interpret=False)
        check("randk_decompress_into", (n, d), written,
              ref.randk_decompress_into_ref(rows, vals, start, jnp.int32(0),
                                            n_rows=n,
                                            block_rows=BLOCK_ROWS), 0.0)
        del written
        flat = [jax.random.normal(k, (n * d + (-(n * d)) % 128,), f32)
                for k in keys[2:6]]
        got = diana_shift_update(*flat, alpha=0.5, beta=0.125,
                                 interpret=False)
        want = ref.diana_shift_update_ref(*flat, 0.5, 0.125)
        for g, w in zip(got, want):
            check("diana_shift_update", (n, d), g, w, 1e-6)
        del flat, got, want, rows

        # the packed wire on the slab this leaf puts on the wire
        slabs = jax.random.normal(keys[6], (ranks, kb * BLOCK_ROWS, d), f32)
        u = jax.random.uniform(keys[7], (ranks, kb * BLOCK_ROWS, d))
        packed, scales = [], []
        for r in range(ranks):
            p, s = pack_slab(slabs[r], u[r], levels=levels, interpret=False)
            p_ref, s_ref = ref.pack_slab_ref(slabs[r], u[r], levels=levels,
                                             block_rows=BLOCK_ROWS)
            # a byte may round the other way, by one level, where the
            # stochastic threshold lands within the last ulp of the
            # kernel's division; that happens about once in 1e7 elements
            check("pack_slab bytes", (kb * BLOCK_ROWS, d), p, p_ref, 1.0,
                  max_share=1e-5)
            check("pack_slab scales", (kb * BLOCK_ROWS, d),
                  s / s_ref, jnp.ones(s.shape, f32), 1e-6)
            packed.append(p)
            scales.append(s)
            check("unpack_slab", (kb * BLOCK_ROWS, d),
                  unpack_slab(p, s, levels=levels, n_rows=kb * BLOCK_ROWS,
                              interpret=False),
                  ref.unpack_slab_ref(p, s, levels=levels,
                                      n_rows=kb * BLOCK_ROWS), 0.0)
        packed, scales = jnp.stack(packed), jnp.stack(scales)
        check("unpack_reduce", (ranks, kb * BLOCK_ROWS, d),
              unpack_reduce(packed, scales, levels=levels,
                            n_rows=kb * BLOCK_ROWS, interpret=False),
              ref.unpack_reduce_ref(packed, scales, levels=levels,
                                    n_rows=kb * BLOCK_ROWS), 1e-6)
        print(f"parity at leaf rows x cols {n} x {d} (slab {kb * BLOCK_ROWS}"
              f" rows): ok")
    for name, err in sorted(worst.items()):
        print(f"parity {name}: max |kernel - oracle| {err:g} over all leaves")


def one_chip(jax, train):
    from repro.configs import get_chip_config

    m = len(jax.devices())
    cfg = get_chip_config(ARCH)
    kernel_parity(jax, cfg, fraction=0.02, ranks=4)
    print("phase: full participation, diana, shared f32 wire")
    train_phase(jax, train, train_argv(m, 5), "step", 5)
    print("phase: fleet, 4 clients per chip in a host RAM store")
    train_phase(jax, train, train_argv(m, 3, "--clients", str(4 * m)),
                "round", 3)


# the two wires of the four-chip comparison: packed8 moves the int8 lattice
# of 127 levels, the f32 wire the same lattice decoded to f32
WIRES = {"packed8": dict(wire_dtype="packed8"),
         "f32 levels=127": dict(wire_dtype="f32", wire_levels=127)}
# what the order of the cross-chip sum may change in the direction after a
# few rounds, relative to the leaf's largest entry (a few f32 ulps a round)
WIRE_RTOL = 2.0 ** -16


def wire_programs(cfg, mesh, rounds: int):
    """Jitted programs for the wire alone: `inputs(key)` makes one random
    gradient per client for the tallest leaf of each width in `cfg`, and
    `exchange[name](grads, key)` runs `rounds` diana rounds of that wire
    inside the fully-manual shard_map, as the train step does, and returns
    each client's direction."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.dist import CompressedAggregation
    from repro.launch.mesh import num_clients
    from repro.launch.steps import configure_agg

    tallest = {}
    for n, d in leaf_row_shapes(cfg):
        tallest[d] = max(n, tallest.get(d, 0))
    shapes = sorted((n, d) for d, n in tallest.items())
    m = num_clients(mesh)
    spec = P("data", None, None)
    sh = NamedSharding(mesh, spec)

    @jax.jit
    def inputs(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.lax.with_sharding_constraint(
            jax.random.normal(k, (m, n, d), jnp.float32), sh)
            for k, (n, d) in zip(keys, shapes)]

    def exchange(agg):
        agg = configure_agg(agg, mesh)

        def body(grads, key):
            grads = [g[0] for g in grads]
            state = agg.init(grads)
            for t in range(rounds):
                direction, state = agg.aggregate(
                    grads, state, jax.random.fold_in(key, t))
            return [x[None] for x in direction]

        specs = [spec] * len(shapes)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P()), out_specs=specs,
            axis_names=set(mesh.axis_names), check_vma=False))

    progs = {name: exchange(CompressedAggregation(
        method="diana", wire="shared", fraction=0.02,
        shift_dtype=jnp.float32, **kw)) for name, kw in WIRES.items()}
    return shapes, inputs, progs


def wire_agreement(jax, cfg, mesh, rounds: int = 3):
    """packed8 against the f32 wire on identical inputs: the two run the
    same pack and unpack kernels and differ only in how the ranks' decoded
    slabs are summed (all_gather + unpack_reduce in rank order, or the
    all-reduce in its own order)."""
    import jax.numpy as jnp

    from repro.core import salts

    key = salts.root_key(0, salts.ROUNDS_KEY_SALT)
    shapes, inputs, progs = wire_programs(cfg, mesh, rounds)
    with jax.set_mesh(mesh):
        grads = inputs(jax.random.fold_in(key, 1))
        a, b = (progs[name](grads, key) for name in WIRES)
    for (n, d), x, y in zip(shapes, a, b):
        diff = jnp.abs(x - y)
        err, scale = float(jnp.max(diff)), float(jnp.max(jnp.abs(y)))
        share = float(jnp.mean((diff > 0).astype(jnp.float32)))
        print(f"wire {rounds} diana rounds at leaf {n} x {d}: "
              f"{share:g} of the direction differs, max |packed8 - f32| "
              f"{err:g} of max |direction| {scale:g}")
        if not scale > 0 or err > WIRE_RTOL * scale:
            raise RuntimeError(f"wire at {n} x {d}: max |diff| {err} over "
                               f"{WIRE_RTOL:g} x {scale}")


def host_params(jax, params):
    import numpy as np

    return [np.asarray(x, np.float32)
            for x in jax.tree.leaves(jax.device_get(params))]


def four_chips(jax, train):
    """packed8 wire against the f32 wire carrying the same levels: 3
    training steps of each, then the wire alone on identical inputs."""
    import numpy as np

    m = len(jax.devices())
    finals, losses, init = {}, {}, None
    for name, kw in WIRES.items():
        wire = [f"--{k.replace('_', '-')}={v}" for k, v in kw.items()]
        print(f"phase: {m} clients, diana, {name} wire")
        tr, args, state, losses[name] = train_phase(
            jax, train, train_argv(m, 3, *wire), "step", 3)
        finals[name] = host_params(jax, state.params)
        del state
        if init is None:
            with jax.set_mesh(tr.mesh):
                state = train.init_state(args, tr)
            init = host_params(jax, state.params)
            del state
    a, b = finals.values()
    n = sum(x.size for x in a)
    differ = sum(int(np.sum(x != y)) for x, y in zip(a, b))
    worst = max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))
    apart = np.sqrt(sum(np.sum(np.square(x - y)) for x, y in zip(a, b)))
    moved = np.sqrt(sum(np.sum(np.square(y - p)) for y, p in zip(b, init)))
    print(f"params after 3 steps: {differ} of {n} differ, max |diff| {worst}"
          f"; |packed8 - f32| {apart:.6g} = {apart / moved:.4g} of "
          f"|f32 - init| {moved:.6g}")
    la, lb = losses.values()
    rel = max(abs(x - y) / abs(y) for x, y in zip(la, lb))
    print(f"losses per step: packed8 {la}, f32 {lb}; max relative diff "
          f"{rel:.3g}")
    print(f"phase: wire alone, {m} clients, packed8 against f32 levels=127")
    wire_agreement(jax, tr.cfg, tr.mesh)
    # The wire check bounds what the sum order changes in one exchange.
    # Over the steps that difference crosses bf16 rounding boundaries of
    # the params and of the bf16 forward pass, so the trained params are
    # held to moving together: apart by less than half the distance they
    # moved, with losses within 1e-4 of each other.
    if not moved > 0 or apart > 0.5 * moved or rel > 1e-4:
        raise RuntimeError("packed8 and f32 levels=127 runs disagree")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip wire comparison")
    opts = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {platform!r}")
    if len(devices) != opts.chips:
        sys.exit(f"chip_smoke: --chips {opts.chips} but {len(devices)} "
                 "devices are attached")

    from repro.launch import train
    from repro.launch.cache import enable_compile_cache

    print(device_line(jax))
    print(f"compile cache: {enable_compile_cache()}")
    (four_chips if opts.chips == 4 else one_chip)(jax, train)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
