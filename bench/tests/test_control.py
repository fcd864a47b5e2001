"""The control, at a size a test run can hold: the plain reference put in
the program's place and computed with fp8 operands (one precision below
the configurations' bfloat16 compute) must fail the limits that the
program itself passes.  The chip readings at the cells' own sizes, and
the limits set from them, are in PERF.md; this keeps the method under
test at the tiny size's limits.
"""
import contextlib

import pytest

from conftest import TRAIN_LIMITS, TRAIN_MIX


def fails(numbers, lim):
    return any(numbers[k] > v for k, v in lim.items())


@pytest.mark.parametrize("config", ["xlstm-tiny", "minicpm-tiny"])
def test_train_control_fails(tiny, config):
    import check
    import spec
    _, write = tiny
    write("t", config, "tmix", mix=TRAIN_MIX, batch=4, reference_rows=2,
          limits=TRAIN_LIMITS)
    c = spec.workload("t")
    job = spec.load_module("jobs", "train")
    st = job.setup(c, 5, lambda name: contextlib.nullcontext())
    job.release(st)
    ref = job.reference(st, c, 5)
    assert not fails(job.compare(st, ref), c["limits"])
    control = job.reference(st, c, 5, prec="fp8")
    assert fails(check.compare_train(control, ref), c["limits"])


def test_serve_control_fails(tiny):
    import spec
    from test_faults import SERVE_LIMITS, SERVE_MIX
    _, write = tiny
    write("s", "xlstm-tiny", "smix", mix=SERVE_MIX, limits=SERVE_LIMITS)
    c = spec.workload("s")
    job = spec.load_module("jobs", "serve")
    st = job.setup(c, 5, lambda name: contextlib.nullcontext())
    job.window(st, c, 1.0, lambda name: contextlib.nullcontext())
    job.release(st)
    assert not fails(job.compare(st, job.reference(st, c, 5)), c["limits"])
    control = job.compare(st, job.reference(st, c, 5, prec="fp8"))
    assert fails(control, c["limits"])
