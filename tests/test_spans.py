"""The training step's named layers (``repro.spans``): every device scope
reaches the compiled HLO's ``op_name`` metadata, which the profiler
records with each device op, and ``Run.step`` opens its host spans in a
profiler trace."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.api import DataSpec, Run, RunSpec
from repro.core import wtacrs_linear
from repro.core.config import EstimatorKind, WTACRSConfig
from repro.core.kernel_config import KernelConfig
from repro.models import common as cm

BACKWARD = "transpose(jvp(forward))"
SCOPES = {
    "xlstm-125m": (spans.FORWARD, spans.OPTIMIZER, spans.WTACRS_PLAN,
                   spans.WTACRS_DW, spans.MLSTM_SCAN, spans.SLSTM_SCAN),
    "minicpm-2b": (spans.FORWARD, spans.OPTIMIZER, spans.WTACRS_PLAN,
                   spans.WTACRS_DW, spans.ATTENTION),
    "kanana-2-30b-a3b": (spans.FORWARD, spans.OPTIMIZER, spans.WTACRS_PLAN,
                         spans.WTACRS_DW, spans.ATTENTION, spans.MOE_ROUTE,
                         spans.MOE_DISPATCH, spans.MOE_EXPERTS),
}


def _op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _under(name, scope):
    """Whether ``scope`` is a component of the name stack ``name``,
    bare or inside a transformation such as ``jvp(...)``."""
    return any(re.sub(r"^(\w+\()+|\)+$", "", part) == scope
               for part in name.split("/"))


def _run(arch):
    pol = cm.Policy(wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS,
                                        budget=0.3, min_rows=2))
    run = Run(RunSpec(arch=arch, policy=pol, steps=4, batch_size=2,
                      data=DataSpec(seq_len=16, n_samples=8)))
    run.init()
    return run


@pytest.fixture(scope="module", params=sorted(SCOPES))
def stepped(request, tmp_path_factory):
    """(arch, op names of the compiled step, host spans of a profiler
    trace of two ``Run.step`` calls)."""
    run = _run(request.param)
    batches = [run.dataset.batch_at(s, 2) for s in range(2)]
    run.step(batches[0])                      # compile outside the trace
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir)
    for b in batches:
        run.step(b)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    host = sorted((e.start_ns, e.name) for p in pd.planes
                  for line in p.lines for e in line.events
                  if e.name.startswith("repro."))
    (fn,) = run.step_fn.compiled.values()
    b = {k: jnp.asarray(v) for k, v in batches[1].items()
         if k != "sample_ids"}
    names = _op_names(fn.lower(run.state, b).compile().as_text())
    return request.param, names, [n for _, n in host]


def test_every_scope_reaches_the_compiled_step(stepped):
    arch, names, _ = stepped
    for scope in SCOPES[arch]:
        assert any(_under(n, scope) for n in names), scope


def test_backward_ops_carry_the_transposed_forward(stepped):
    """The weight gradient runs in the backward, the plan in the
    forward; the update is outside both."""
    _, names, _ = stepped
    dw = [n for n in names if _under(n, spans.WTACRS_DW)]
    assert dw and all(BACKWARD in n for n in dw)
    plan = [n for n in names if _under(n, spans.WTACRS_PLAN)]
    assert any(BACKWARD not in n for n in plan)
    opt = [n for n in names if _under(n, spans.OPTIMIZER)]
    assert opt and not any(_under(n, spans.FORWARD) for n in opt)


def test_expert_gemms_and_their_dw_sit_under_moe_experts(stepped):
    """The held experts' weight gradients run in the backward inside
    ``moe_experts``; routing and dispatch are scopes of their own."""
    arch, names, _ = stepped
    if spans.MOE_EXPERTS not in SCOPES[arch]:
        assert not any(_under(n, spans.MOE_EXPERTS) for n in names)
        return
    experts = [n for n in names if _under(n, spans.MOE_EXPERTS)]
    assert any(_under(n, spans.WTACRS_DW) and BACKWARD in n
               for n in experts)
    assert any(BACKWARD not in n for n in experts)
    assert not any(_under(n, spans.MOE_DISPATCH) or _under(n, spans.MOE_ROUTE)
                   for n in experts)


def test_run_step_opens_its_host_spans(stepped):
    _, _, host = stepped
    one = [spans.STEP_PUT, spans.STEP_DISPATCH, spans.STEP_FETCH]
    assert host == one + one


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_wtacrs_dw_scopes_both_routes(backend):
    """Under either kernel backend the dW contraction is a dot_general
    inside the ``wtacrs_dw`` scope, and the Pallas dW kernel is nowhere
    in the compiled gradient."""
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (2, 32, 128))
    w = jax.random.normal(jax.random.fold_in(key, 1), (128, 128)) * 0.1
    cfg = WTACRSConfig(budget=0.25, min_rows=8,
                       kernel=KernelConfig(backend=backend))
    grad = jax.jit(jax.grad(lambda w_: jnp.sum(wtacrs_linear(
        h, w_, key=jax.random.PRNGKey(1), cfg=cfg))))
    text = grad.lower(w).compile().as_text()
    names = _op_names(text)
    dw = [n for n in names if _under(n, spans.WTACRS_DW)]
    assert any(n.endswith("/dot_general") for n in dw)
    plan = [n for n in names if _under(n, spans.WTACRS_PLAN)]
    assert plan and not any(_under(n, spans.WTACRS_DW) for n in plan)
    assert "fused_sampled_dw" not in text
