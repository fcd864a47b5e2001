"""Device time per training step of the expert layer's routing and data
movement, forward and backward: the program's ``moe_route`` (router,
selection, weights) and ``moe_dispatch`` (sort, gather of the held
experts' rows, weighted combine) scopes."""
import namestack

UNIT = "ms"


def read(ctx):
    secs = namestack.seconds(ctx, "moe_route", "moe_dispatch")
    if secs <= 0 or ctx["steps"] < 1:
        return None
    return 1e3 * secs / ctx["steps"]
