"""Unified kernel dispatch configuration.

Before this module, kernel routing was scattered across two surfaces
that had to be kept in sync by hand: ``WTACRSConfig.use_kernel`` (a
bool that only said *whether* to use Pallas) and per-call
``bm``/``bn``/``bk``/``interpret`` keyword arguments on every
``repro.kernels.ops`` wrapper (which said *how*, but were invisible to
the policy layer and recomputed ``jax.default_backend()`` on every
call inside jit-traced code).  :class:`KernelConfig` replaces both:
one frozen, hashable record that rides inside ``WTACRSConfig`` (and
therefore through policies, rules, and the ``RunSpec`` façade) and is
consumed by every kernel dispatch site.

On the training step the config routes one kernel: the row-norm pass
that feeds the sampling plans (``core.plans.batched_row_weights``).
The sampled weight gradient always runs as XLA's ``dot_general``
(``core.linear._sampled_dw``); its Pallas kernel,
``kernels.ops.fused_sampled_dw``, is a standalone op that still takes
this config (blocks, tuning table) when called directly.

Resolution:

* ``interpret`` — ``None`` means "whether JAX's default backend is the
  CPU", read by :attr:`KernelConfig.interpreted` at dispatch.  The
  field itself never changes, so the config's hash/equality (it is a
  jit static argument via custom_vjp ``nondiff_argnums``) is stable for
  the life of the process, and constructing a config — including the
  module-level defaults below — does not initialize a JAX backend.
* ``backend`` — ``"pallas"`` forces the Pallas kernels (interpreted on
  CPU: the correctness path CI exercises), ``"jnp"`` forces the pure
  jnp fallbacks, ``"auto"`` picks Pallas exactly when it would compile
  natively (i.e. not in interpret mode).

Block sizes are *optional overrides*: ``None`` defers to the autotuner
(``repro.kernels.autotune``) when ``autotune=True``, else to the
shape-derived defaults.  ``table_path`` points the autotuner at a
persisted tuning table (``None`` = the table packaged with
``repro.kernels``).
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
    """How (and whether) the Pallas kernels serve a sampled linear's
    row norms, and the standalone kernel ops that take it.

    Attributes:
      backend: ``"auto"`` (Pallas when compiling natively, jnp under
        interpret-mode backends), ``"pallas"`` (always the kernels —
        interpreted on CPU), ``"jnp"`` (always the jnp fallbacks).
      bm / bn / bk: optional block-size overrides for the sampled
        backward GEMM grid ``(d_in/bm, d_out/bn, B, k/bk)``.  ``None``
        defers to the tuning table / defaults.
      block_rows / block_d: optional overrides for the row-norm and
        gather kernels' tiling.
      autotune: consult the persisted tuning table for unset blocks.
      table_path: tuning-table JSON (``None`` = packaged default).
      interpret: run kernels through the Pallas interpreter.  ``None``
        means ``jax.default_backend() == "cpu"`` (see ``interpreted``).
    """

    backend: str = "auto"
    bm: Optional[int] = None
    bn: Optional[int] = None
    bk: Optional[int] = None
    block_rows: Optional[int] = None
    block_d: Optional[int] = None
    autotune: bool = True
    table_path: Optional[str] = None
    interpret: Optional[bool] = None

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"unknown kernel backend {self.backend!r}; "
                             f"one of {_BACKENDS}")
        for f in ("bm", "bn", "bk", "block_rows", "block_d"):
            v = getattr(self, f)
            if v is not None and (not isinstance(v, int) or v < 1):
                raise ValueError(f"KernelConfig.{f} must be a positive "
                                 f"int or None, got {v!r}")

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

    def with_backend(self, backend: str) -> "KernelConfig":
        return dataclasses.replace(self, backend=backend)

    def block_overrides(self) -> dict:
        """The explicitly pinned GEMM blocks (subset of bm/bn/bk)."""
        return {f: getattr(self, f) for f in ("bm", "bn", "bk")
                if getattr(self, f) is not None}


# The config every dispatch site falls back to when the caller does not
# thread one through.
DEFAULT_KERNEL_CONFIG = KernelConfig()

# The correctness-path config CI and the parity tests use: force the
# kernels even on CPU (Pallas interpreter).
PALLAS_INTERPRET_CONFIG = KernelConfig(backend="pallas")
