"""Names of the training step's layers, as a profiler trace shows them.

Traced code opens ``jax.named_scope(<name>)`` with the device scope
names below; each name then sits in the ``op_name`` metadata of every
HLO op traced inside it (the name stack, e.g.
``jit(train_step)/transpose(jvp(forward))/wtacrs_dw/dot_general``), and
the profiler records that stack with each device op.  Scopes change no
computation.  Under ``jax.value_and_grad`` the forward's ops carry
``jvp(forward)`` and the backward's ``transpose(jvp(forward))``,
rematerialized recompute included, so the backward needs no scope of
its own.

Host spans (``host_span``) are ``jax.profiler.TraceAnnotation``: they
cost an enter and an exit on the host and are recorded only while a
profiler is tracing.
"""
from __future__ import annotations

import jax

# device scopes (jax.named_scope)
FORWARD = "forward"            # the loss, under value_and_grad
OPTIMIZER = "optimizer"        # parameter and optimizer-state update
WTACRS_PLAN = "wtacrs_plan"    # row weights, probabilities, sampling, H'
WTACRS_DW = "wtacrs_dw"        # the sampled weight-gradient contraction
ATTENTION = "attention"        # flash attention
MLSTM_SCAN = "mlstm_scan"      # the mLSTM recurrence over tokens
SLSTM_SCAN = "slstm_scan"      # the sLSTM recurrence over tokens
MOE_ROUTE = "moe_route"        # router, expert selection and weights
MOE_DISPATCH = "moe_dispatch"  # sort, gather of the held experts' rows,
#                                and the weighted combine
MOE_EXPERTS = "moe_experts"    # the held experts' GEMMs and their dW

# host spans of Run.step
STEP_PUT = "repro.step.put"            # the batch's upload
STEP_DISPATCH = "repro.step.dispatch"  # step driver and the jitted call
STEP_FETCH = "repro.step.fetch"        # metrics to the host: the sync

host_span = jax.profiler.TraceAnnotation
