"""Pallas TPU kernels for the WTA-CRS hot spots.

Kernels (each: <name>.py kernel body, ops.py KernelConfig-dispatched
wrapper, ref.py oracle):
  * fused_sampling -- sampled backward dW = sum_b H'_b^T
                      (dZ_b[idx_b]*scale_b) as one XLA gather+scale
                      fusion and one ragged-k contraction kernel,
                      blocks from the autotune tuning table; a
                      standalone op (the training step runs the same
                      gather and XLA's dot_general, core.linear)
  * sampled_matmul -- legacy even-tiling form of the same contraction
                      (host-pads H'/dZ); retained as the fused path's
                      parity/benchmark reference
  * row_norms      -- per-row L2 norms feeding the column-row
                      distribution (plans.batched_row_weights): the
                      kernel the training step runs
  * gather_scale   -- scalar-prefetched sub-sample gather (build H')
  * flash_attention -- fused online-softmax attention fwd (serving path;
                       p-blocks stay in VMEM -- the §Perf next-step fix)
  * autotune       -- (bm, bn, bk) block-size search + persisted JSON
                      tuning table keyed on (d_in, d_out, B, k, dtype)

Dispatch policy lives in :class:`repro.core.kernel_config.KernelConfig`
(backend auto|pallas|jnp, block overrides, tuning-table path) — one
frozen record threaded from RunSpec/Policy down to every wrapper.
"""
from repro.kernels import autotune, ops, ref

__all__ = ["autotune", "ops", "ref"]
