"""Device time per training step of the parameter and optimizer-state
update, the program's ``optimizer`` scope."""
import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_step(ctx, "optimizer")
