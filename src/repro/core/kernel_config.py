"""Kernel dispatch configuration.

The sampled weight gradient is a gather plus XLA's ``dot_general``
(``core.linear._sampled_dw``) on every backend.  :class:`KernelConfig`
routes one step of a sampled linear: the row-norm pass that feeds the
sampling plans (``core.plans.batched_row_weights``), either the Pallas
kernel (``kernels.ops.row_norms``) or an f32-accumulating einsum.  It
is one frozen, hashable record that rides inside ``WTACRSConfig`` and
therefore through policies, rules and the ``RunSpec`` façade.

Resolution:

* ``interpret`` — ``None`` means "whether JAX's default backend is the
  CPU", read by :attr:`KernelConfig.interpreted` at dispatch.  The
  field itself never changes, so the config's hash/equality (it is a
  jit static argument via custom_vjp ``nondiff_argnums``) is stable for
  the life of the process, and constructing a config — including the
  module-level default below — does not initialize a JAX backend.
* ``backend`` — ``"pallas"`` forces the kernel (interpreted on CPU:
  the correctness path CI exercises), ``"jnp"`` forces the einsum,
  ``"auto"`` picks Pallas exactly when it would compile natively (i.e.
  not in interpret mode), which is on a TPU.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

_BACKENDS = ("auto", "pallas", "jnp")


def _on_cpu() -> bool:
    import jax
    return jax.default_backend() == "cpu"


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Whether a sampled linear's row norms run on the Pallas kernel.

    Attributes:
      backend: ``"auto"`` (Pallas when compiling natively, jnp under
        interpret-mode backends), ``"pallas"`` (always the kernel —
        interpreted on CPU), ``"jnp"`` (always the einsum).
      interpret: run kernels through the Pallas interpreter.  ``None``
        means ``jax.default_backend() == "cpu"`` (see ``interpreted``).
    """

    backend: str = "auto"
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown kernel backend {self.backend!r}; "
                             f"one of {_BACKENDS}")

    @property
    def interpreted(self) -> bool:
        """Whether the kernels run through the Pallas interpreter."""
        return _on_cpu() if self.interpret is None else self.interpret

    @property
    def use_pallas(self) -> bool:
        """Whether dispatch routes through the Pallas kernels."""
        if self.backend == "pallas":
            return True
        if self.backend == "jnp":
            return False
        return not self.interpreted      # auto: only when compiled natively


# The config every dispatch site falls back to when the caller does not
# thread one through.
DEFAULT_KERNEL_CONFIG = KernelConfig()
