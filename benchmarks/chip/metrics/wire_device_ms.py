"""Device time per round of the compressed exchange, in ms: the operations
the program's `wire` scope names (compression, collectives, the Rand-block
and DIANA kernels, the shift updates); on several chips, the slowest."""
import scopes


def read(record, trace):
    return scopes.device_ms(record, trace, lambda c: c == "wire")
