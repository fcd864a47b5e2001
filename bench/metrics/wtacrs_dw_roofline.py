"""Roofline share of the sampled weight-gradient contraction, read from
the program's ``wtacrs_dw`` scope whatever route implements it (the
Pallas kernel with its dZ gather, or the gather and a dot_general): the
least time of the same work as ``sampled_dw_roofline``
(``flops.sampled_dw``) over the device time of every op in the scope."""
import scopes

UNIT = "%"


def read(ctx):
    secs = scopes.Scoped.of(ctx).seconds("wtacrs_dw")
    fl, by = ctx["flops"].sampled_dw(ctx["cell"], ctx["model"])
    if secs <= 0 or fl <= 0:
        return None
    pk = ctx["peaks"]
    least = max(fl / pk["flops_bf16"], by / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx["steps"] / secs
