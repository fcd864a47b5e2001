"""Device time per training step of WTA-CRS plan building, the program's
``wtacrs_plan`` scope: row weights, probabilities, sampling and the
gather of the kept rows H', in the forward and in any recompute."""
import scopes

UNIT = "ms"


def read(ctx):
    return scopes.ms_per_step(ctx, "wtacrs_plan")
