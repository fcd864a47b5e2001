"""Device time per training step of attention, forward and backward: the
program's ``attention`` scope."""
import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_step(ctx, "attention")
