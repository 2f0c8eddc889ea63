"""The fused DIANA update's share of its HBM roofline, in %: the bytes it
must move per round (flops.diana_shift_bytes) over the chip's peak
bandwidth, against the kernel's summed device time per round. It moves
bytes, not FLOPs, so bandwidth bounds it."""

KERNEL = "diana_shift_update"


def read(record, trace):
    times = [sum(v for k, v in c["op_s"].items() if k.startswith(KERNEL))
             for c in trace["chips"]]
    if not all(t > 0 for t in times):
        return None
    need = record["rounds"] * record["kernel_bytes_per_round"][
        "diana_shift"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / max(times)
