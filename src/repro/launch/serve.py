"""Production serving driver: prefill a request batch, stream decode.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --tokens 16

It runs the reduced config on a mesh of the attached devices (set no XLA
flags here; a CPU run forces host devices on its command line);
--production-mesh builds the pod mesh with the full config (requires
hardware / the dry-run's forced host devices).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, reduced
from repro.core import salts
from repro.launch import steps
from repro.launch.mesh import make_attached_mesh, make_production_mesh
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="stablelm-1.6b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        cfg = get_config(args.arch)
    else:
        mesh = make_attached_mesh()
        cfg = reduced(get_config(args.arch), seq=max(64, 2 * args.prompt_len))
    key = salts.root_key(0, salts.SERVE_KEY_SALT)
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

        def sample(lg, k):
            lg = lg[:, :, :cfg.vocab]
            if args.temperature <= 0:
                return jnp.argmax(lg, -1).astype(jnp.int32)
            return jax.random.categorical(
                k, lg / args.temperature, axis=-1).astype(jnp.int32)

        tok = sample(logits, key)
        out = [tok]
        t0 = time.time()
        for i in range(args.tokens):
            key, sk = jax.random.split(key)
            logits, cache = jitted(params, cache, jax.device_put(tok, tsh),
                                   jnp.int32(args.prompt_len + i))
            tok = sample(logits, sk)
            out.append(tok)
        dt = (time.time() - t0) / args.tokens
    gen = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} | {dt*1e3:.1f} ms/token")
    print("request 0 token ids:", gen[0].tolist())


if __name__ == "__main__":
    main()
