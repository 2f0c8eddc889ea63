"""Share of the window in which no operation runs on the device, in %,
from the trace; on several chips, the most idle one."""


def read(record, trace):
    window = trace["window_s"]
    if not window > 0:
        return None
    return 100.0 * max(1.0 - c["busy_s"] / window for c in trace["chips"])
