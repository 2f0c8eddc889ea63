"""The reduction from a profiler trace to per-layer numbers."""
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

import xtrace

RECORDED = Path(__file__).resolve().parent / "testdata" / "v5e_small.xplane.pb"


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def brute_busy(intervals, t0, t1):
    mask = np.zeros(t1 - t0, bool)
    for s, e in intervals:
        mask[max(s, t0) - t0:max(min(e, t1) - t0, 0)] = True
    return int(mask.sum())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_union_and_subtract_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    a = [(int(s), int(s + d)) for s, d in zip(rng.integers(0, 900, 40),
                                                rng.integers(1, 60, 40))]
    b = [(int(s), int(s + d)) for s, d in zip(rng.integers(0, 900, 25),
                                                rng.integers(1, 60, 25))]
    ua, ub = xtrace.union(a), xtrace.union(b)
    assert xtrace.length(ua) == brute_busy(a, 0, 1000)
    only_a = np.zeros(1000, bool)
    for s, e in a:
        only_a[s:e] = True
    for s, e in b:
        only_a[s:e] = False
    assert xtrace.length(xtrace.subtract(ua, ub)) == int(only_a.sum())


def test_reduce_synthetic_two_chips():
    host = plane("/host:CPU", t=[ev("bench_window", 100, 1100),
                                 ev("round", 100, 600), ev("wait", 150, 600),
                                 ev("round", 600, 1100),
                                 ev("next_batch", 600, 700)])
    chip0 = plane("/device:TPU:0",
                  XLA_Ops=[ev("fusion.1", 0, 300), ev("shift_kernel", 300, 400),
                           ev("all-reduce.2", 350, 500), ev("fusion.3", 800, 1000)],
                  XLA_Modules=[ev("jit_step", 0, 500), ev("jit_step", 800, 1000)])
    chip1 = plane("/device:TPU:1",
                  XLA_Ops=[ev("fusion.1", 100, 1100)],
                  XLA_Modules=[ev("jit_step", 100, 1100)])
    got = xtrace.reduce([host, chip1, chip0])
    assert got["window_s"] == pytest.approx(1000e-9)
    c0, c1 = got["chips"]
    assert c0["busy_s"] == pytest.approx(600e-9)  # 100-500 and 800-1000
    assert c1["busy_s"] == pytest.approx(1000e-9)
    assert c0["op_s"]["shift_kernel"] == pytest.approx(100e-9)
    assert c0["module_s"]["jit_step"] == pytest.approx(600e-9)
    assert c0["module_calls"]["jit_step"] == 2
    assert c0["collective_exposed_s"] == pytest.approx(100e-9)  # 400-500
    # gaps on chip 0: 500-800 (mid 650, in next_batch), 1000-1100 (round)
    assert c0["gaps"][0] == ("next_batch", pytest.approx(300e-9))
    assert c0["gaps"][1] == ("round", pytest.approx(100e-9))
    assert got["busy_s"] == pytest.approx(800e-9)


def test_reduce_needs_the_window():
    with pytest.raises(ValueError, match="bench_window"):
        xtrace.reduce([plane("/host:CPU", t=[ev("round", 0, 5)])])


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData

    planes = [plane(p.name, **{line.name.replace(" ", "_"): [
        ev(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]
        for line in p.lines}) for p in ProfileData.from_file(
            str(RECORDED)).planes]
    got = xtrace.reduce(planes)
    window = [e for p in planes if p.name.startswith("/host")
              for line in p.lines for e in line.events
              if e.name == "bench_window"]
    assert len(window) == 1
    t0, t1 = int(window[0].start_ns), int(window[0].end_ns)
    assert got["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    chips = [p for p in planes if xtrace.DEVICE.fullmatch(p.name)]
    assert len(chips) == len(got["chips"]) >= 1
    ops = [(e.start_ns, e.end_ns) for line in chips[0].lines
           if line.name == "XLA Ops" for e in line.events]
    assert got["chips"][0]["busy_s"] == pytest.approx(
        brute_busy(ops, t0, t1) * 1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    kernel = sum(v for k, v in got["chips"][0]["op_s"].items()
                 if k.startswith("diana_shift_update"))
    assert kernel > 0
