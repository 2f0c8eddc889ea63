"""The on-chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload stablelm.diana.full \
        --seed 12345 --seconds 45 --trace 0

Everything a cell is made of is data found by name: the workload's entry
in `BENCHMARK.json`, its configuration file, `traffic/<traffic>.json`,
`limits/<workload>.json`, and one reader `metrics/<metric>.py` for each
per-layer metric. Only a TPU is accepted, with exactly the chips the cell
asks for, and a device kind listed in `peaks.py`.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the end-to-end metrics, or with
`--trace 1` the per-layer ones), `device`, with `--trace 1` a `breakdown`,
and last `checks`, each compared number beside its limit; the checks are
also the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
GiB = 2 ** 30


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(bench: dict, workload: str):
    """(cell, configuration, traffic, limits) for a workload name."""
    from cell import load_json

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, load_json(ROOT / conf["file"]),
            load_json(HERE / "traffic" / f"{cell['traffic']}.json"),
            load_json(HERE / "limits" / f"{workload}.json"))


def metrics_for(bench: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def read_metric(name: str, record: dict, trace: dict):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record, trace)


def end_to_end(record: dict) -> dict:
    rounds = record["round_s"]
    return {
        "setup_s": record["setup_s"],
        "tokens_per_s": record["tokens"] / record["window_s"],
        "round_p90_ms": 1e3 * statistics.quantiles(rounds, n=10)[-1]
        if len(rounds) >= 2 else rounds[0] * 1e3,
        "peak_hbm_gib": record["memory_peak_bytes"] / GiB,
    }


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from cell import load_json

    bench = load_json(ROOT / "BENCHMARK.json")
    cell_entry, conf, traffic, limits = load_cell(bench, args.workload)

    import repro  # noqa: F401  (the system under test must be present)
    import jax

    from peaks import peaks_for
    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found platform {platform!r}")
    if len(devices) != cell_entry["chips"]:
        raise SystemExit(f"{args.workload} asks for {cell_entry['chips']} "
                         f"chips; {len(devices)} are attached")
    peaks = peaks_for(devices[0].device_kind)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import cell
    import xtrace

    trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) \
        if args.trace else None
    try:
        record = cell.run(conf, traffic, limits, seed=args.seed,
                          seconds=args.seconds, devices=devices,
                          t_start=T_START, trace_dir=trace_dir)
        record["peaks"] = peaks
        device = {"platform": platform, "kind": devices[0].device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": record["memory_peak_bytes"]}
        out = {"correct": record["correct"] and record["finite"],
               "attempted": record["rounds"],
               "failed": 0 if record["finite"] else record["rounds"]}
        if args.trace:
            trace = xtrace.reduce_dir(trace_dir)
            values = {}
            for spec in metrics_for(bench, args.workload, "per_layer"):
                v = read_metric(spec["name"], record, trace)
                if v is not None:
                    values[spec["name"]] = {"value": v, "unit": spec["unit"]}
            out["metrics"] = values
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            out["device"] = device
            out["breakdown"] = {"device_ops": trace["top_ops"],
                                "idle_gaps": trace["top_gaps"]}
        else:
            e2e = end_to_end(record)
            out["metrics"] = {
                spec["name"]: {"value": e2e[spec["name"]],
                               "unit": spec["unit"]}
                for spec in metrics_for(bench, args.workload, "end_to_end")}
            out["device"] = device
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    out["checks"] = record["checks"]
    if record["phase_map_s"] is not None:
        print(f"phase_map_s {record['phase_map_s']!r}", file=sys.stderr)
    print("readings " + json.dumps(record["readings"]), file=sys.stderr)
    print("gaps " + json.dumps(record["gaps"]), file=sys.stderr)
    for name, c in record["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
