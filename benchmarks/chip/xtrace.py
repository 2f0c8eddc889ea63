"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

The window is the host's `bench_window` annotation, which the harness puts
around the measured rounds. On each TPU plane (`/device:TPU:<n>`) the
events of the "XLA Ops" line are the device's operations and those of
"XLA Modules" its compiled programs. Per chip, clipped to the window:

- busy: the union of the operations' intervals; idle is the rest;
- op time: summed duration by operation name, the HLO instruction's
  (a Pallas kernel's is its function name, `diana_shift_update.3`);
  control-flow operations (`while.7`) contain the ones they run;
- module time: summed duration by program name;
- exposed collective time: the part of collective operations' intervals
  that no other operation on that chip covers;
- the ten longest idle gaps, each labelled with the innermost harness annotation on the
  host that spans the gap's midpoint ("untraced" when none does);
- idle time inside each of the program's spans (`spans.idle_in_span`).
"""
from __future__ import annotations

import re
from pathlib import Path

WINDOW = "bench_window"
LABELS = ("round", "fleet_round", "next_batch", "dispatch", "wait")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather", re.I)
DEVICE = re.compile(r"/device:TPU:(\d+)")
# control-flow ops span the operations they run: left out of the top list
CONTROL = ("while", "conditional", "call")


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list[tuple[int, int]]:
    """Merged intervals `a` minus merged intervals `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(events, t0, t1):
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            yield name, s, e


def short_name(name: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    return [(short_name(ev.name), int(ev.start_ns), int(ev.end_ns))
            for ev in line.events]


def chip_numbers(lines: dict, t0: int, t1: int, labels) -> dict:
    ops = list(_clip(_events(lines["XLA Ops"]), t0, t1)) \
        if "XLA Ops" in lines else []
    mods = list(_clip(_events(lines["XLA Modules"]), t0, t1)) \
        if "XLA Modules" in lines else []
    busy = union((s, e) for _, s, e in ops)
    op_time, mod_time = {}, {}
    for name, s, e in ops:
        op_time[name] = op_time.get(name, 0) + (e - s)
    for name, s, e in mods:
        mod_time[name] = mod_time.get(name, 0) + (e - s)
    coll = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
    other = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
    gaps = sorted(subtract([(t0, t1)], busy), key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:10]:
        mid = (s + e) // 2
        inside = [(le - ls, name) for name, ls, le in labels
                  if ls <= mid < le]
        labelled.append((min(inside)[1] if inside else "untraced",
                         (e - s) * 1e-9))
    return {"busy_s": length(busy) * 1e-9,
            "op_s": {k: v * 1e-9 for k, v in op_time.items()},
            "module_s": {k: v * 1e-9 for k, v in mod_time.items()},
            "module_calls": {k: sum(1 for n, _, _ in mods if n == k)
                             for k in mod_time},
            "collective_exposed_s": length(subtract(coll, other)) * 1e-9,
            "gaps": labelled}


def reduce(planes) -> dict:
    """`planes`: the trace's planes (ProfileData.planes)."""
    from spans import idle_in_span

    planes = list(planes)
    window, labels, chips = None, [], {}
    for plane in planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == WINDOW and (window is None
                                           or e - s > window[1] - window[0]):
                        window = (s, e)
                    elif name in LABELS:
                        labels.append((name, s, e))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation on the host")
    t0, t1 = window
    for plane in planes:
        hit = DEVICE.fullmatch(plane.name)
        if hit:
            lines = {line.name: line for line in plane.lines}
            chips[int(hit.group(1))] = chip_numbers(lines, t0, t1, labels)
    if not chips:
        raise ValueError("no TPU device plane in the trace")
    per_chip = [chips[k] for k in sorted(chips)]
    for c, idle in zip(per_chip, idle_in_span(planes)):
        c["idle_in_span"] = idle
    ops = {}
    for c in per_chip:
        for k, v in c["op_s"].items():
            if not k.startswith(CONTROL):
                ops[k] = ops.get(k, 0.0) + v / len(per_chip)
    gaps = sorted((g for c in per_chip for g in c["gaps"]),
                  key=lambda g: -g[1])
    return {"window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(c["busy_s"] for c in per_chip) / len(per_chip),
            "chips": per_chip,
            "top_ops": sorted(([k, v] for k, v in ops.items()),
                              key=lambda kv: -kv[1])[:10],
            "top_gaps": [[n, s] for n, s in gaps[:10]]}


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(find_xplane(trace_dir))).planes)


def describe(trace_dir, events: int = 5) -> None:
    """Print the planes, lines and a few events of a trace, to read one
    by hand: `python3 benchmarks/chip/xtrace.py DIR`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(trace_dir)))
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:events]
            print(f"  line {line.name!r}: {len(evs)} events; {top}")
            for ev in evs[:2]:
                print(f"    {ev.name[:200]!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns}")


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
