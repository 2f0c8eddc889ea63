"""Record the small TPU trace the reduction's tests read
(`testdata/v5e_small.xplane.pb`).

    python3 benchmarks/chip/record_trace.py benchmarks/chip/testdata

Runs on a TPU: a few rounds of a small jitted matmul program and of the
program's fused DIANA kernel, with the harness's annotations around them,
under the JAX profiler; copies the trace's `.xplane.pb` to the directory
given.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(out_dir: str) -> None:
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    import jax
    import jax.numpy as jnp

    from repro.kernels.diana_shift import diana_shift_update

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    step = jax.jit(lambda x, w: jnp.tanh(x @ w) @ w.T)
    x = jnp.ones((1024, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 1e-3
    flat = [jnp.full((1 << 20,), v, jnp.float32) for v in (1.0, 2.0, 3.0, 4.0)]
    jax.block_until_ready((step(x, w), diana_shift_update(
        *flat, alpha=0.5, beta=0.25, interpret=False)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("round"):
                with jax.profiler.TraceAnnotation("dispatch"):
                    y = step(x, w)
                    out = diana_shift_update(*flat, alpha=0.5, beta=0.25,
                                             interpret=False)
                with jax.profiler.TraceAnnotation("wait"):
                    jax.block_until_ready((y, out))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    shutil.copy(src, Path(out_dir) / "v5e_small.xplane.pb")
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
