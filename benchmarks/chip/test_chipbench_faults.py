"""A whole run of each cell's path at a tiny size on the CPU, with the
chip look skipped: sound, then with the timed step broken underneath;
`correct` must come out false for every fault the cell can have."""
import time

import jax
import pytest

import cell
from chipbench_tiny import TINY_LIMITS, tiny_config, tiny_traffic

CASES = {"stablelm.diana.full": ("stablelm-1.6b-chip", "diana.full"),
         "stablelm.diana.fleet": ("stablelm-1.6b-chip", "diana.fleet"),
         "starcoder2.diana.full": ("starcoder2-15b-chip", "diana.full")}


def run_tiny(workload, fault=None):
    conf, traffic = CASES[workload]
    return cell.run(tiny_config(conf), tiny_traffic(traffic),
                    TINY_LIMITS, seed=2 ** 33 + 17, seconds=0.0,
                    devices=jax.devices()[:1],
                    t_start=time.perf_counter(), fault=fault)


@pytest.mark.parametrize("workload", sorted(CASES))
def test_sound_run_is_correct(workload):
    rec = run_tiny(workload)
    assert rec["correct"], rec["checks"]
    assert rec["rounds"] >= 1 and rec["finite"]


@pytest.mark.parametrize("fault", cell.FAULTS)
@pytest.mark.parametrize("workload", ["stablelm.diana.full",
                                      "stablelm.diana.fleet"])
def test_fault_is_caught(workload, fault):
    rec = run_tiny(workload, fault)
    assert not rec["correct"], rec["checks"]


def test_reference_model_is_found_by_name(tmp_path, monkeypatch):
    """A copy of `references/dense.py` under another name, named by the
    configuration, gives the same readings and gaps as `dense` itself."""
    conf, traffic = CASES["stablelm.diana.full"]
    args = dict(traffic=tiny_traffic(traffic), limits=TINY_LIMITS,
                seed=2 ** 33 + 19, seconds=0.0, devices=jax.devices()[:1])
    base = tiny_config(conf)
    got = cell.run(base, t_start=time.perf_counter(), **args)
    (tmp_path / "plain_copy.py").write_text(
        (cell.REFERENCES / "dense.py").read_text())
    monkeypatch.setattr(cell, "REFERENCES", tmp_path)
    moved = cell.run({**base, "reference": "plain_copy"},
                     t_start=time.perf_counter(), **args)
    assert moved["correct"], moved["checks"]
    assert moved["readings"] == got["readings"]
    assert moved["gaps"] == got["gaps"]
    assert moved["flops_per_token"] == got["flops_per_token"]
