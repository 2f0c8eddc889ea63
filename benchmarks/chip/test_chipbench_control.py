"""The control at a tiny size on the CPU: the reference computed on
float8 operands in the program's place, and with half of the rows left
out, must fail the cell's limits, while the program passes them."""
import jax
import pytest

import compare
from chipbench_tiny import TINY_LIMITS, tiny_config, tiny_traffic
from control import readings

CASES = {"stablelm.diana.full": ("stablelm-1.6b-chip", "diana.full"),
         "starcoder2.diana.full": ("starcoder2-15b-chip", "diana.full")}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_control_fails_and_program_passes(workload):
    conf, traffic = CASES[workload]
    lim = TINY_LIMITS
    got = readings(tiny_config(conf), tiny_traffic(traffic), lim,
                   seed=7, devices=jax.devices()[:1])
    assert compare.judge(got["program"], lim)[0], got["program"]
    assert not compare.judge(got["fp8"], lim)[0], got["fp8"]
    assert not compare.judge(got["half_batch"], lim)[0], got["half_batch"]
