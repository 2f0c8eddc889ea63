"""Device idle time per round while the step's caller waits for its batch,
in ms: the idle time that overlaps the program's `input_wait` spans
(`data/pipeline.py`), on the profiler's clock; on several chips, the
largest. None when the program records no such span."""


def read(record, trace):
    per_chip = [c.get("idle_in_span", {}).get("input_wait")
                for c in trace["chips"]]
    if any(v is None for v in per_chip):
        return None
    return 1e3 * max(per_chip) / record["rounds"]
