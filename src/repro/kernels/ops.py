"""Public wrappers around the Pallas kernels, dispatched by KernelConfig.

The training step reaches one kernel here: :func:`row_norms`, the
row-norm pass of the sampling plans (``core.plans.batched_row_weights``).
``KernelConfig.backend`` routes only that pass; the sampled weight
gradient is a gather plus XLA's ``dot_general`` on every backend
(``core.linear._sampled_dw``).  ``use_pallas`` routes to the kernel
(run through the interpreter when ``KernelConfig.interpreted``),
anything else to the pure-jnp oracle in :mod:`repro.kernels.ref`.
``kernel=None`` means ``DEFAULT_KERNEL_CONFIG`` (backend ``auto``:
Pallas exactly when compiling natively, jnp on interpret-mode/CPU
backends).

:func:`flash_attention_fwd` always runs its Pallas kernel; no training
or serving step calls it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.kernel_config import DEFAULT_KERNEL_CONFIG, KernelConfig
from repro.kernels import ref as _ref
from repro.kernels import row_norms as _norms
from repro.launch import mesh as mesh_lib

# Row-norm tiling: (ROW_BLOCK, LANE_BLOCK) is the block each grid step
# reads, snapped to the TPU's (sublane, 128-lane) tile by the rules
# below.
LANE = 128
ROW_BLOCK = 256
LANE_BLOCK = 512


def sublane(dtype) -> int:
    """Rows per (sublane, lane) tile: 8 for 32-bit dtypes, 16 for 16-bit
    ones (two rows pack into one sublane)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def lane_block(dim: int, want: int) -> int:
    """Tile-legal block along a minor (lane) dim that divides ``dim``:
    the largest multiple of 128 dividing ``dim`` that is <= ``want``,
    else the smallest one above it, else the full dim."""
    legal = [b for b in range(LANE, dim + 1, LANE) if dim % b == 0]
    if not legal:
        return dim
    fits = [b for b in legal if b <= want]
    return max(fits) if fits else legal[0]


def sublane_block(n: int, want: int, dtype) -> int:
    """Tile-legal row block: all ``n`` rows when n <= ``want``, else the
    largest multiple of :func:`sublane` that is <= ``want`` (at least
    one tile).  It need not divide n: the caller pads the rows."""
    sub = sublane(dtype)
    if n <= want:
        return n
    return min(n, max(sub, want // sub * sub))


def _resolve(kernel: KernelConfig | None) -> KernelConfig:
    return DEFAULT_KERNEL_CONFIG if kernel is None else kernel


def _per_data_shard(fn, x):
    """Run the kernel call ``fn(x)`` on each data shard.

    The compiler cannot partition a Mosaic kernel, so under an ambient
    mesh of more than one device the call runs inside ``shard_map``: the
    leading (row) dim of ``x`` and of the output is split over the
    mesh's data axes (``launch.mesh.data_axes``) and the other dims are
    whole on each device.

    The mesh is the ambient one: a step jitted over sharded arrays must
    run under ``jax.set_mesh`` (``Run.step`` does), or the kernel call
    reaches the partitioner whole and the compiler refuses it.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn(x)
    spec = P(mesh_lib.data_axes(mesh) or None)
    # check_vma=False: pallas_call outputs carry no varying-axes type
    return jax.shard_map(fn, in_specs=spec, out_specs=spec,
                         check_vma=False)(x)


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


@functools.partial(jax.jit, static_argnames=("kernel",))
def row_norms(x: jax.Array, *,
              kernel: KernelConfig | None = None) -> jax.Array:
    """Per-row L2 norms (f32) of (n, d)."""
    cfg = _resolve(kernel)
    if not cfg.use_pallas:
        return _ref.row_norms_ref(x)

    def local(x):
        n, d = x.shape
        # tile-legal blocks: block_d divides d (no column padding), rows
        # are padded to a multiple of block_rows
        block_rows = sublane_block(n, ROW_BLOCK, x.dtype)
        block_d = lane_block(d, LANE_BLOCK)
        out = _norms.row_norms(_pad_rows(x, block_rows),
                               block_rows=block_rows, block_d=block_d,
                               interpret=cfg.interpreted)
        return out[:n]

    return _per_data_shard(local, x)


@functools.partial(jax.jit, static_argnames=("group", "causal", "bq", "bk",
                                             "kernel"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        group: int = 1, causal: bool = True,
                        bq: int = 128, bk: int = 128,
                        kernel: KernelConfig | None = None) -> jax.Array:
    """Fused flash attention forward (serving path); see
    kernels/flash_attention.py.  q: (BH, Sq, Dh), k/v: (BKVH, Skv, Dh).

    Always runs the Pallas kernel (there is no sampling to skip);
    ``kernel`` only supplies the ``interpreted`` resolution.  bq/bk
    stay explicit: flash tiling is sequence-length geometry.
    """
    from repro.kernels import flash_attention as _fl
    cfg = _resolve(kernel)
    return _fl.flash_attention_fwd(q, k, v, group=group, causal=causal,
                                   bq=bq, bk=bk, interpret=cfg.interpreted)
