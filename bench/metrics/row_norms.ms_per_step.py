"""Device time per training step of the row-norm kernel that starts
every WTA-CRS plan (``kernels/row_norms``)."""
UNIT = "ms"
KERNEL = r"row_norms"


def read(ctx):
    secs = ctx["trace"].op_seconds(KERNEL)
    if secs <= 0 or ctx["steps"] < 1:
        return None
    return 1e3 * secs / ctx["steps"]
