"""The expert kernels' share of their roofline, in %: the least time
their work could take on the chip over the time they took
(`experts_device_ms`). The work is that of the running cell's
configuration (`run.py --workload`, its `model` section) at the expected
routed rows, as its reference model's `expert_kernel_work` counts it
(`references/moonlight.py`): per step, FLOPs 2 * rows * d_model * d_ff
for each of 12 grouped products per expert layer (the forward three
twice under full remat, both cotangents of each), and the bytes each must
move (the held experts' matrices, its rows in and out). The least time is
the larger of FLOPs over the peak bf16 FLOP/s and bytes over the peak HBM
bandwidth; which of the two it is goes to standard error. None where the
program runs no expert kernel or the reference counts no expert work."""
import argparse
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


def _load(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_config(argv=None) -> dict:
    """The configuration file of the cell that `--workload` names."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    workload = ap.parse_known_args(argv)[0].workload
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return json.loads((ROOT / conf["file"]).read_text())


def least_seconds(record, conf) -> tuple[float, str] | None:
    """(least seconds per step on one chip, the bound that sets it), or
    None where the configuration's reference counts no expert work."""
    ref = _load(f"reference_{conf['reference']}",
                HERE / "references" / f"{conf['reference']}.py")
    if not hasattr(ref, "expert_kernel_work"):
        return None
    tokens = record["tokens"] / (record["rounds"] * record["chips"])
    flops, nbytes = ref.expert_kernel_work(conf["model"], tokens)
    peaks = record["peaks"]
    compute = flops / peaks["bf16_flops"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return max(compute, memory), "compute" if compute >= memory else "memory"


def read(record, trace, argv=None):
    ms = _load("experts_device_ms", Path(__file__).with_name(
        "experts_device_ms.py")).read(record, trace)
    if ms is None:
        return None
    least = least_seconds(record, cell_config(argv))
    if least is None:
        return None
    print(f"experts_roofline bound {least[1]} least_ms {1e3 * least[0]!r}",
          file=sys.stderr)
    return 100.0 * 1e3 * least[0] / ms
