"""Batched decode serving on a (data, model) mesh: prefill a prompt batch,
then stream tokens through the sharded serve_step (KV cache donated
in-place each step).

    PYTHONPATH=src python examples/serve_decode.py --arch starcoder2-15b --tokens 32

Uses the REDUCED config of the chosen architecture so the example runs on
CPU; the full config is exercised (lower+compile) by launch/dryrun.py.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, reduced
from repro.launch import steps
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="starcoder2-15b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch), seq=max(64, args.prompt_len * 2))
    mesh = make_test_mesh((4, 2), ("data", "model"))
    key = jax.random.key(0)
    params = T.init_params(key, cfg)
    cache_len = args.prompt_len + args.tokens + 8

    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (args.batch, cfg.vision_patches, cfg.d_model), cfg.dtype)
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model), cfg.dtype)

    serve, lower_args = steps.make_serve_step(cfg, mesh)
    with jax.set_mesh(mesh):
        logits, cache = T.prefill(params, batch, cfg, cache_len=cache_len)
        jitted, (psh, csh, tsh) = lower_args(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache),
            jax.ShapeDtypeStruct((args.batch, 1), jnp.int32),
        )
        params = jax.device_put(params, psh)
        cache = jax.device_put(cache, csh)
        tok = jnp.argmax(logits[:, :, :cfg.vocab], -1).astype(jnp.int32)
        out = [tok]
        t0 = time.time()
        for i in range(args.tokens):
            logits, cache = jitted(params, cache, jax.device_put(tok, tsh),
                                   jnp.int32(args.prompt_len + i))
            tok = jnp.argmax(logits[:, :, :cfg.vocab], -1).astype(jnp.int32)
            out.append(tok)
        dt = (time.time() - t0) / args.tokens
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} (reduced) | batch={args.batch} | "
          f"{dt*1e3:.1f} ms/token on CPU")
    print("generated token ids (first request):", gen[0].tolist())
    assert bool(jnp.all((gen >= 0) & (gen < cfg.vocab)))
    print("OK: all generated ids in-vocab; cache ring/state advanced "
          f"{args.tokens} steps")


if __name__ == "__main__":
    main()
