"""The whole training step's share of the chip's bf16 peak: the FLOPs the
cell's estimator requires per step (``flops.train_step_flops``) times
the steps in the traced window, over the window and the chips' peak."""
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if ctx["steps"] < 1 or t.window_s <= 0 or not t.devices:
        return None
    fl = ctx["flops"].train_step_flops(ctx["cell"], ctx["model"])
    return 100.0 * fl * ctx["steps"] / t.window_s / (
        len(t.devices) * ctx["peaks"]["flops_bf16"])
