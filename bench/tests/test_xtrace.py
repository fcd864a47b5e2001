"""The trace reduction, checked on a small trace recorded on a TPU v5e
(``record_trace.py``: three steps of one bf16 matmul and one row-norm
kernel call, with 20 ms of host sleep between them) and on a made-up
trace with nested and overlapping ops."""
import os

import pytest

import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace")


@pytest.fixture(scope="module")
def small():
    return xtrace.Trace.load(DATA)


def test_small_trace_ops_and_window(small):
    assert small.devices == [0]
    # 3 x (copy-start, copy-done, fusion, row_norms, reduce), none nested
    assert len(small.ops[0]) == 15
    total = sum(du for _, _, du in small.ops[0]) / 1e9
    assert small.busy_s() == pytest.approx(total, rel=1e-12)
    assert small.window_s == pytest.approx(0.065706406, rel=1e-9)
    assert small.op_seconds(r"^row_norms$") == pytest.approx(4.7154e-05)
    names = [n for n, _ in small.top_ops()]
    assert names[:2] == ["fusion", "row_norms"]
    secs, runs = small.module_seconds(r"^jit__lambda")
    assert runs == 6 and secs == pytest.approx(total, rel=0.01)


def test_small_trace_idle_is_window_minus_busy(small):
    gaps = dict(small.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(
        small.window_s - small.busy_s(), rel=1e-9)
    assert max(gaps, key=gaps.get) == "bench.wait"


def test_nested_and_overlapping_ops_count_once():
    ms = 1_000_000
    ops = {0: [
        ("%while.3 = (s32[]) while(...)", 0, 10 * ms),
        ("%fusion.1 = f32[8] fusion(...)", 1 * ms, 2 * ms),
        ("%fused_sampled_dw.4 = f32[8] custom-call(...)", 4 * ms, 3 * ms),
        ("%fusion.2 = f32[8] fusion(...)", 20 * ms, 5 * ms),
    ]}
    host = [("bench.step", 0, 12 * ms), ("bench.batch", 12 * ms, 8 * ms),
            ("bench.step", 20 * ms, 6 * ms)]
    t = xtrace.Trace(ops, {}, host)
    assert t.window_s == pytest.approx(0.026)
    assert t.busy_s() == pytest.approx(0.015)          # 0-10 and 20-25
    assert dict(t.idle_gaps()) == pytest.approx(
        {"bench.batch": 0.010, "bench.step": 0.001})
    assert dict(t.top_ops()) == pytest.approx(
        {"fusion": 0.007, "fused_sampled_dw": 0.003})   # no while
    assert t.op_seconds(r"sampled_dw") == pytest.approx(0.003)
