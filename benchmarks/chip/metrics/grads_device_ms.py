"""Device time per round of the clients' gradients, in ms: the operations
the program's `client_grads` scope names (forward, backward and the
recomputed forward), by `scopes.op_scopes` of the compiled step; on several
chips, the slowest."""
import scopes


def read(record, trace):
    return scopes.device_ms(record, trace,
                            lambda c: c.startswith("client_grads"))
