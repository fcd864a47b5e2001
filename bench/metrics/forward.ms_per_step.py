"""Device time per training step of the forward pass: ops under the
program's ``forward`` scope and not under its transpose."""
import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_step(ctx, "forward")
