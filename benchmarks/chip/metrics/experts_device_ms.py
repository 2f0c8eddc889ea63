"""Device time per round of the expert kernels, in ms: the operations the
program names `moe_gmm*` (the grouped products forward and for the input
cotangent) and `moe_tgmm*` (for the weight cotangent); on several chips,
the slowest. None where the program runs no such kernel."""

KERNELS = ("moe_gmm", "moe_tgmm")


def read(record, trace):
    per_chip = [sum(v for k, v in c["op_s"].items() if k.startswith(KERNELS))
                for c in trace["chips"]]
    if not any(per_chip):
        return None
    return 1e3 * max(per_chip) / record["rounds"]
