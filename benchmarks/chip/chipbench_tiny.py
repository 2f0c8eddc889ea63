"""Tiny stand-ins of the benchmark's configurations, for the CPU tests:
the same architectures and traffic keys at toy widths."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def tiny_config(name: str) -> dict:
    """The configuration file `name` with its widths cut to toy size, naming
    the same reference model."""
    import jax.numpy as jnp

    from repro.configs import get_config

    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    kv = 1 if conf["model"]["num_kv_heads"] < conf["model"]["num_heads"] \
        else 4
    over = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=kv,
                head_dim=32, d_ff=256, vocab=512)
    if conf["model"]["sliding_window"]:
        over["sliding_window"] = 16
    cfg = dataclasses.replace(get_config(conf["arch"]), **over)
    model = {k: getattr(cfg, k) for k in conf["model"]}
    model["dtype"] = jnp.dtype(cfg.dtype).name
    return {"name": f"tiny-{name}", "arch": conf["arch"], "overrides": over,
            "model": model, "reference": conf.get("reference")}


def tiny_traffic(name: str) -> dict:
    traffic = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    traffic["seq"] = 32
    return traffic


# Limits for the toy sizes: the cells' own limits were set from readings at
# the cells' sizes, where the bf16 rounding's share of a gap differs. At
# these sizes sound runs read loss gaps near 1e-4 and leaf gaps near 5e-3.
TINY_LIMITS = {"loss": 2e-3, "grad": 0.05, "shift": 0.05, "change": 0.05}
