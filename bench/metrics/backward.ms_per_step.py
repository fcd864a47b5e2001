"""Device time per training step of the backward pass: ops under
``transpose(jvp(forward))``, rematerialized recompute included."""
import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_step(ctx, "backward")
