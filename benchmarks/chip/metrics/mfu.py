"""Model FLOPs of the tokens trained in the window over the window's
seconds, the chips and the chip's peak bf16 FLOP/s, in %. The FLOPs per
token are the configuration's reference model's `flops_per_token`
(`references/<name>.py`), which counts no recomputation."""


def read(record, trace):
    del trace
    peak = record["peaks"]["bf16_flops"]
    return 100.0 * record["tokens"] * record["flops_per_token"] / (
        record["window_s"] * record["chips"] * peak)
