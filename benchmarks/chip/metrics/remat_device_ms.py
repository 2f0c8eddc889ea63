"""Device time per round of the forward recomputed under remat, in ms: the
`client_grads` operations on a `rematted_computation` path; on several
chips, the slowest. A part of `grads_device_ms`."""
import scopes


def read(record, trace):
    return scopes.device_ms(record, trace,
                            lambda c: c == "client_grads/remat")
