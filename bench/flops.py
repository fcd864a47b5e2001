"""Operations and bytes a training step requires, from its shapes.

Counted once, as the equations require: no recompute.  Per linear of
input width d_in and output width d_out over B sequences of S tokens,
the forward and the input gradient are 2 B S d_in d_out each; the weight
gradient is 2 B k d_in d_out, with k the rows the estimator keeps
(k = S where it is exact).  The recurrences and attention come from the
reference model's ``mixer_flops``.
"""
from __future__ import annotations


def k_rows(mix: dict, seq_len: int) -> int:
    if mix["estimator"] == "exact":
        return seq_len
    return min(max(8, int(round(mix["budget"] * seq_len))), seq_len)


def train_step_flops(cell, model) -> float:
    cfgd, mix = cell["config_file"]["config"], cell["mix"]
    b, s = cell["batch"], mix["seq_len"]
    k = k_rows(mix, s)
    tot = 0.0
    for _, di, do, n, sampled in model.linears(cfgd):
        rows = k if sampled else s
        tot += n * (4 * b * s * di * do + 2 * b * rows * di * do)
    return tot + b * model.mixer_flops(cfgd, s)


def sampled_dw(cell, model, operand_bytes=2, out_bytes=4):
    """(FLOPs, bytes) per step of the sampled weight-gradient
    contractions: each reads the k gathered rows of H and of dZ of every
    sequence and writes one f32 d_in x d_out result."""
    cfgd, mix = cell["config_file"]["config"], cell["mix"]
    b, s = cell["batch"], mix["seq_len"]
    k = k_rows(mix, s)
    fl = by = 0.0
    for _, di, do, n, sampled in model.linears(cfgd):
        if sampled and mix["estimator"] != "exact" and k < s:
            fl += n * 2 * b * k * di * do
            by += n * (b * k * (di + do) * operand_bytes + di * do * out_bytes)
    return fl, by


def peaks(device_kind: str) -> dict:
    import json
    from pathlib import Path
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["kinds"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table["kinds"][device_kind]
