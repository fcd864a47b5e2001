"""The scoped reduction (``scopes.py``) and the readers built on it,
checked on small traces recorded on a TPU v5e: ``small_trace`` (no
scopes: a program as the parent of the scopes was) and
``scoped_trace`` (``record_scoped_trace.py``: tiny training cells of the
scoped program), and on made-up traces."""
import json
import os
import shutil

import pytest

import flops
import scopes
import spec
import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small_trace")
SCOPED = os.path.join(DATA, "scoped_trace")
NEW = ["wtacrs_dw_roofline", "wtacrs_plan.ms_per_step",
       "forward.ms_per_step", "backward.ms_per_step",
       "optimizer.ms_per_step", "attention.ms_per_step",
       "recurrent_scan.ms_per_step", "step_host_idle.ms_per_step"]
MS = 1_000_000


def _ctx(cell, trace_dir, root, monkeypatch):
    """A reader's context as ``bench/run.py`` builds it, with the trace
    where the run keeps it (``.bench_trace/<cell>`` under the root)."""
    monkeypatch.setattr(spec, "ROOT", root)
    shutil.copytree(trace_dir, root / ".bench_trace" / cell["name"])
    return {"trace": xtrace.Trace.load(trace_dir), "cell": cell,
            "steps": cell["steps"], "work": {}, "flops": flops,
            "peaks": flops.peaks(cell["device_kind"]),
            "model": spec.load_module("reference",
                                      cell["config_file"]["reference"])}


def _read(ctx, names):
    return {n: spec.load_module("metrics", n).read(ctx) for n in names}


@pytest.fixture(scope="module")
def cells():
    with open(os.path.join(SCOPED, "cells.json")) as f:
        return json.load(f)


def test_tf_op_of_each_device_op():
    tf_ops, clashes, host = scopes.read_xspace(xtrace.xplane_file(SMALL))
    by_op = {xtrace.op_name(k): v for k, v in tf_ops.items()}
    assert by_op["row_norms"] == "jit(<lambda>)/jit(row_norms)/pallas_call:"
    assert by_op["fusion"] == "jit(<lambda>)/dot_general:"
    assert "copy-start" not in by_op          # XLA's own: no name stack
    assert clashes == 0 and host == []


def test_unscoped_program_reads_nothing(cells, tmp_path, monkeypatch):
    """The small trace's program has no scopes and no ``repro.*`` spans
    (as a program before them): every new reader reports nothing."""
    cell = dict(cells["minicpm-tiny.ft"], name="small", steps=3)
    ctx = _ctx(cell, SMALL, tmp_path, monkeypatch)
    assert _read(ctx, NEW) == dict.fromkeys(NEW)
    assert _read(ctx, ["row_norms.ms_per_step"])["row_norms.ms_per_step"]


@pytest.mark.parametrize("name", ["xlstm-tiny.ft", "minicpm-tiny.ft",
                                  "minicpm-tiny.ft.jnp"])
def test_scoped_cells(cells, name, tmp_path, monkeypatch):
    cell = cells[name]
    ctx = _ctx(cell, os.path.join(SCOPED, name), tmp_path, monkeypatch)
    got = _read(ctx, NEW + ["sampled_dw_roofline", "row_norms.ms_per_step"])
    xlstm = name.startswith("xlstm")
    want = set(NEW) - {"attention.ms_per_step" if xlstm
                       else "recurrent_scan.ms_per_step"}
    assert {k for k in NEW if got[k] is not None} == want
    sc = scopes.Scoped.of(ctx)
    assert sc.clashes == 0
    steps = cell["steps"]
    t = ctx["trace"]
    busy_ms = 1e3 * t.busy_s() / steps
    split = sum(got[f"{p}.ms_per_step"] for p in scopes.STEP_PARTS)
    assert 0.9 * busy_ms <= split <= busy_ms
    idle_ms = 1e3 * (t.window_s - t.busy_s()) / steps
    assert 0 <= got["step_host_idle.ms_per_step"] <= idle_ms
    assert sc.skew_ms() is not None
    if name.endswith(".jnp"):       # the same work read on the other route
        assert got["sampled_dw_roofline"] is None
        assert got["row_norms.ms_per_step"] is None
    else:                           # each scope holds the older metric's op
        assert got["wtacrs_dw_roofline"] <= got["sampled_dw_roofline"]
        assert (got["wtacrs_plan.ms_per_step"]
                >= got["row_norms.ms_per_step"])


@pytest.mark.parametrize("tf_op,cats", [
    ("jit(train_step)/jvp(forward)/while/body/closed_call/wtacrs_plan/"
     "vmap(jit(argsort))/sort:", ("forward", "wtacrs_plan")),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "mlstm_scan/while/body/closed_call/checkpoint/rematted_computation/"
     "mul:", ("backward", "mlstm_scan")),
    ("jit(<lambda>)/transpose(jvp(wtacrs_dw))/jit(fused_sampled_dw):",
     ("wtacrs_dw",)),
    ("jit(train_step)/optimizer/mul:", ("optimizer",)),
    ("jit(train_step)/jvp(forward)/attention_out/dot_general:",
     ("forward",)),
    ("jit(forwarding)/add:", ()),
    ("", ()),
])
def test_categories(tf_op, cats):
    assert scopes.categories(tf_op) == cats


def test_idle_gap_goes_to_the_span_overlapping_most():
    """The 10-18 ms gap goes to ``repro.step.put``, which holds most of
    it, though its midpoint falls in ``repro.step.dispatch`` (the clocks
    differ by about a millisecond); a span counts only its own time,
    less the spans nested in it."""
    ops = {0: [("%fusion.1 = f32[8] fusion(...)", 0, 10 * MS),
               ("%fusion.2 = f32[8] fusion(...)", 18 * MS, 8 * MS),
               ("%fusion.3 = f32[8] fusion(...)", 30 * MS, 10 * MS)]}
    bench = [("bench.step", 0, 29 * MS), ("bench.batch", 29 * MS, 2 * MS),
             ("bench.step", 31 * MS, 9 * MS)]
    t = xtrace.Trace(ops, {}, bench)
    host = [("repro.step.put", 10 * MS, 4 * MS),
            ("repro.step.dispatch", 14 * MS, 2 * MS),
            ("repro.step.fetch", 18 * MS, 10 * MS)]
    sc = scopes.Scoped(t, {}, 0, host)
    assert sc.idle_by_span() == pytest.approx(
        {"repro.step.put": 0.008, "repro.step.fetch": 0.004})
    assert scopes._own_time(0, 29 * MS, bench + host) == [
        (0, 10 * MS), (16 * MS, 18 * MS), (28 * MS, 29 * MS)]


def test_host_spans_on_the_profiler_clock(tmp_path):
    """``repro.*`` spans read from the protobuf carry the times
    ``jax.profiler.ProfileData`` gives them."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((64, 64))
    jax.profiler.start_trace(str(tmp_path), profiler_options=xtrace.options())
    for name in ("repro.a", "repro.b", "bench.c"):
        with jax.profiler.TraceAnnotation(name):
            (x @ x).block_until_ready()
    jax.profiler.stop_trace()
    path = xtrace.xplane_file(str(tmp_path))
    _, _, host = scopes.read_xspace(path)
    pd = jax.profiler.ProfileData.from_file(path)
    want = sorted(((e.name, e.start_ns, e.duration_ns) for p in pd.planes
                   for line in p.lines for e in line.events
                   if e.name.startswith("repro.")), key=lambda e: e[1])
    assert [n for n, _, _ in host] == ["repro.a", "repro.b"]
    assert host == [(n, pytest.approx(s, abs=1), pytest.approx(d, abs=1))
                    for n, s, d in want]
