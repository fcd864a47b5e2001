"""The expert layer's readers (``namestack.py``): on the recorded v5e
traces of programs without an expert layer they report nothing, and a
scope's seconds agree with ``scopes.py``'s reading of the same trace."""
import os

import pytest

import namestack
import scopes
import spec
from test_scopes import SCOPED, _ctx, _read, cells  # noqa: F401

MOE = ["moe_dispatch.ms_per_step", "moe_experts_roofline"]


@pytest.mark.parametrize("name", ["xlstm-tiny.ft", "minicpm-tiny.ft"])
def test_scopes_agree_and_moe_readers_stay_silent(cells, name, tmp_path,
                                                  monkeypatch):
    cell = cells[name]
    ctx = _ctx(cell, os.path.join(SCOPED, name), tmp_path, monkeypatch)
    assert _read(ctx, MOE) == dict.fromkeys(MOE)
    sc = scopes.Scoped.of(ctx)
    for scope in ("wtacrs_plan", "wtacrs_dw", "optimizer"):
        assert namestack.seconds(ctx, scope) == pytest.approx(
            sc.seconds(scope), rel=1e-9)
    assert namestack.seconds(ctx, "moe_experts", "moe_dispatch") == 0
    # an op taken by its name counts once, scoped or not
    by_name = namestack.seconds(ctx, op_prefixes=("row_norms",))
    assert by_name > 0
    assert namestack.seconds(ctx, "wtacrs_plan", op_prefixes=(
        "row_norms",)) == pytest.approx(sc.seconds("wtacrs_plan"))


def test_roofline_needs_the_models_work(cells, tmp_path, monkeypatch):
    """A reference model without ``moe_experts_work`` reads nothing."""
    cell = cells["minicpm-tiny.ft"]
    ctx = _ctx(cell, os.path.join(SCOPED, "minicpm-tiny.ft"), tmp_path,
               monkeypatch)
    assert not hasattr(ctx["model"], "moe_experts_work")
    reader = spec.load_module("metrics", "moe_experts_roofline")
    assert reader.read(ctx) is None
