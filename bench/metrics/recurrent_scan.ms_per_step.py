"""Device time per training step of the recurrences over tokens, forward
and backward: the program's ``mlstm_scan`` and ``slstm_scan`` scopes."""
import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_step(ctx, "mlstm_scan", "slstm_scan")
