"""End-to-end driver: train a ~100M-param LM with the production DIANA-RR
compressed-gradient wire on a (data=4, model=2) mesh of 8 host devices.

This is deliverable (b)'s end-to-end example: real mesh, real shard_map
train step (per-client grads -> Rand-block compression -> sparse all-reduce
-> DIANA shift update -> SGD), random-reshuffling data pipeline, loss
falling on a learnable synthetic token stream.

    PYTHONPATH=src python examples/train_lm_diana_rr.py --preset tiny --steps 60
    PYTHONPATH=src python examples/train_lm_diana_rr.py --preset 100m --steps 300
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dist import CompressedAggregation
from repro.data.pipeline import make_batch_stream, shared_slots_for_step
from repro.data.reshuffle import ReshuffleSampler
from repro.data.tokens import synthetic_token_batches
from repro.launch import steps
from repro.launch.mesh import make_test_mesh, num_clients
from repro.models.config import ArchConfig

PRESETS = {
    # ~10M: CI-speed sanity run
    "tiny": dict(num_layers=4, d_model=256, num_heads=4, num_kv_heads=4,
                 d_ff=1024, vocab=2048),
    # ~100M-class model (the deliverable's end-to-end scale)
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=12,
                 d_ff=3072, vocab=8192),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)  # global; 2 per client
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--fraction", type=float, default=0.05)
    ap.add_argument("--agg", choices=("diana_rr", "diana", "q", "dense"),
                    default="diana_rr",
                    help="diana_rr is the paper's Algorithm 3 on the wire: "
                         "per-slot shift tables + the shared (rr_shared) "
                         "reshuffling order")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = ArchConfig(name=f"lm-{args.preset}", family="dense",
                     norm="rmsnorm", act="swiglu", **PRESETS[args.preset])
    mesh = make_test_mesh((4, 2), ("data", "model"))
    m = num_clients(mesh)
    n_batches = 8
    slotted = args.agg == "diana_rr"
    agg = CompressedAggregation(method=args.agg, wire="shared",
                                fraction=args.fraction,
                                n_slots=n_batches if slotted else 1,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=args.lr, remat=False)

    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abstract.params))
    print(f"model: {n_params/1e6:.1f}M params | clients={m} | agg={args.agg} "
          f"(k/d={args.fraction}) | mesh=(data=4, model=2)")

    # random-reshuffling data pipeline (the paper's 'RR' — a data-pipeline
    # property). DIANA-RR uses the SHARED per-epoch order so every client
    # sits on the same shift-table slot each round (DESIGN.md §3.8).
    data = synthetic_token_batches(
        vocab=cfg.vocab, seq_len=args.seq, batch=args.batch // m,
        num_batches=n_batches, num_clients=m, seed=0)
    sampler = ReshuffleSampler(m, n_batches,
                               mode="rr_shared" if slotted else "rr", seed=1)

    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m), shardings)
        key = jax.random.key(1)
        t0 = time.time()
        first = last = None
        # epoch-indexed RR stream: client-major rows, prefetch+device_put
        # overlapped with the running step (data.pipeline, DESIGN.md §3.7)
        stream = make_batch_stream(
            {"tokens": data}, sampler,
            put=lambda b: jax.device_put(b, batch_sh(b)))
        with stream:
            for t, batch in zip(range(args.steps), stream):
                if slotted:
                    slots = jnp.asarray(shared_slots_for_step(
                        sampler, t, n_slots=agg.n_slots))
                    state, metrics = jitted(state, batch, key, slots)
                else:
                    state, metrics = jitted(state, batch, key)
                if t % args.log_every == 0 or t == args.steps - 1:
                    loss = float(metrics["loss"])
                    first = first if first is not None else loss
                    last = loss
                    print(f"step {t:4d} | loss {loss:7.4f} | "
                          f"gnorm {float(metrics['grad_norm']):8.3f} | "
                          f"{(time.time()-t0)/(t+1):5.2f}s/step", flush=True)
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'DECREASED' if last < first - 0.05 else 'no significant change'})")


if __name__ == "__main__":
    main()
