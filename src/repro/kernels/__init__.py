"""Pallas TPU kernels.

Kernels (each: <name>.py kernel body, ops.py KernelConfig-dispatched
wrapper, ref.py oracle):
  * row_norms       -- per-row L2 norms feeding the column-row
                       distribution (plans.batched_row_weights): the
                       kernel the training step runs
  * flash_attention -- fused online-softmax attention forward; no step
                       calls it yet

The sampled weight gradient has no kernel: it is a gather plus XLA's
dot_general (``core.linear._sampled_dw``), and
:class:`repro.core.kernel_config.KernelConfig` (backend
auto|pallas|jnp, interpret mode) routes only the row norms.  ``ref``
also holds the sampled-dW oracles that tests compare the step against.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
