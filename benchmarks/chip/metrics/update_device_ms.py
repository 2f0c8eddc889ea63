"""Device time per round of the server's update, in ms: the operations the
program's `server_update` scope names; on several chips, the slowest. Where
the compiler fuses the update into another phase's operation, the fusion
is that phase's and this reads less."""
import scopes


def read(record, trace):
    return scopes.device_ms(record, trace, lambda c: c == "server_update")
