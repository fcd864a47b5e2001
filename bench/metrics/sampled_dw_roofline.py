"""Roofline share of the sampled weight-gradient contraction kernel: the
least time its work could take (the larger of FLOPs over the bf16 peak
and bytes over HBM bandwidth, ``flops.sampled_dw`` from the cell's
shapes) over the device time of the kernel's events."""
UNIT = "%"
KERNEL = r"sampled_dw"


def read(ctx):
    secs = ctx["trace"].op_seconds(KERNEL)
    fl, by = ctx["flops"].sampled_dw(ctx["cell"], ctx["model"])
    if secs <= 0 or fl <= 0:
        return None
    pk = ctx["peaks"]
    least = max(fl / pk["flops_bf16"], by / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx["steps"] / secs
