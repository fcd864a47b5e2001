"""Roofline share of the held experts' GEMMs: the least time of their
work at the expected routed rows (the reference model's
``moe_experts_work``: forward up/gate/down, their input gradients, the
sampled weight gradients over each plan's k rows) over the device time
of every op under the program's ``moe_experts`` scope, whatever
implements it, and of XLA's ragged-dot ops, which carry no name stack
(``namestack``).  Padding rows of the expert buffer that cost device
time lower the share."""
import namestack

UNIT = "%"


def read(ctx):
    work = getattr(ctx["model"], "moe_experts_work", None)
    secs = namestack.seconds(ctx, "moe_experts", op_prefixes=("ragged-dot",))
    if work is None or secs <= 0 or ctx["steps"] < 1:
        return None
    fl, by = work(ctx["cell"])
    pk = ctx["peaks"]
    least = max(fl / pk["flops_bf16"], by / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx["steps"] / secs
