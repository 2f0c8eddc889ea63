"""Meet the program's own spans with each chip's idle time.

While a `repro.telemetry` sink is installed, every span of the program
enters a profiler annotation of its bare name, so the spans land on the
trace's host plane on the device's clock. `idle_in_span(planes)` gives,
per chip, the idle time inside the host's `bench_window` that overlaps
the union of each span's host intervals, on any thread: how long the chip
sat idle while the caller waited for its batch (`input_wait`), the worker
built one (`assemble`), or the fleet host gathered, fetched or scattered
shift rows.

The spans are found by name (`SPANS`); none of them is a harness label
(`xtrace.LABELS`), so the gap labels `xtrace.reduce` prints ignore them.
"""
from __future__ import annotations

from xtrace import (DEVICE, WINDOW, _clip, _events, length, subtract,
                    union)

SPANS = ("input_wait", "assemble", "gather", "step_dispatch", "shift_fetch",
         "scatter", "page_in", "checkpoint")


def host_spans(planes):
    """(window, {span name: [(start, end), ...]}) from the host planes;
    window is None when no `bench_window` annotation is there."""
    window, spans = None, {}
    for plane in planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW and (window is None
                                           or e - s > window[1] - window[0]):
                        window = (s, e)
                    elif name in SPANS:
                        spans.setdefault(name, []).append((s, e))
    return window, spans


def idle_in_span(planes) -> list[dict[str, float]]:
    """Per TPU chip, in the order of their indices, {span name: idle
    seconds in the window inside that span}; only spans the trace holds.
    Empty when the trace has no window or no TPU plane."""
    planes = list(planes)
    window, spans = host_spans(planes)
    if window is None:
        return []
    t0, t1 = window
    inside = {name: union(iv) for name, iv in spans.items()}
    chips = {}
    for plane in planes:
        hit = DEVICE.fullmatch(plane.name)
        if hit is None:
            continue
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        busy = union((s, e) for ln in ops
                     for _, s, e in _clip(_events(ln), t0, t1))
        idle = subtract([(t0, t1)], busy)
        chips[int(hit.group(1))] = {
            name: (length(idle) - length(subtract(idle, iv))) * 1e-9
            for name, iv in inside.items()}
    return [chips[k] for k in sorted(chips)]
