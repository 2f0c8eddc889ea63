"""Readings that set a cell's limits: the program's gaps to the reference
on many seeds (the lower readings), and the gaps of the control and of a
planted fault in the reference put in the program's place (the upper
readings), all in one process.

    python3 benchmarks/chip/control.py --workload stablelm.diana.full \
        --seeds 11,12,13

Per seed it runs the cell as `run.py` does with the shortest window, then
the reference twice more on the same rows and keys: with every matmul of
the configuration's reference model on float8 e4m3 operands (the control:
one precision below the configuration's bfloat16), and with half of each
client's rows left out (the fault). A
state left unchanged reads 1 on grad, shift and change by construction and
needs no run. The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
VARIANTS = {"fp8": {"fp8": True}, "half_batch": {"half_batch": True}}


def readings(conf, traffic, limits, seed, devices) -> dict:
    """{program, fp8, half_batch}: each the gaps to the reference."""
    import cell
    import compare
    import reference

    rec = cell.run(conf, traffic, limits, seed=seed, seconds=0.0,
                   devices=devices, t_start=time.perf_counter())
    ref = rec["readings"]["reference"]
    model = cell.reference_model(conf)
    out = {"program": rec["gaps"]}
    for name, kw in VARIANTS.items():
        got = reference.run(model, conf["model"], *rec["keys"],
                            rec["feeds"], **rec["wire"], **kw)
        out[name] = compare.gaps(got, ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    import jax

    import run
    from cell import load_json
    from repro.launch.cache import enable_compile_cache

    bench = load_json(HERE.parents[1] / "BENCHMARK.json")
    entry, conf, traffic, limits = run.load_cell(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != entry["chips"]:
        raise SystemExit(f"needs {entry['chips']} TPU chips")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed, **readings(
            conf, traffic, limits, seed, devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
