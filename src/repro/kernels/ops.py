"""Public wrappers around the Pallas kernels, dispatched by KernelConfig.

Every sampling-pipeline entry point takes one optional ``kernel=``
argument — a frozen :class:`repro.core.kernel_config.KernelConfig` —
instead of the old scatter of per-call ``bm``/``bn``/``bk``/
``block_rows``/``block_d``/``interpret`` keywords.  The config decides

  * the backend: ``use_pallas`` routes to the Pallas kernels (run
    through the interpreter when ``KernelConfig.interpreted``), anything
    else to the pure-jnp oracles in :mod:`repro.kernels.ref`;
  * the blocks: explicit config overrides beat the persisted tuning
    table (``repro.kernels.autotune``) beat shape-derived defaults.

``kernel=None`` means ``DEFAULT_KERNEL_CONFIG`` (backend ``auto``:
Pallas exactly when compiling natively, jnp on interpret-mode/CPU
backends).  Tests and CI pass ``PALLAS_INTERPRET_CONFIG`` to force the
kernels through the interpreter.

On the training step only :func:`row_norms` is reached (the plan's
row-norm pass, ``core.plans.batched_row_weights``); the sampled weight
gradient is XLA's dot_general in ``core.linear._sampled_dw``, and
:func:`fused_sampled_dw` here is a standalone op with its tests.

Padding policy: the legacy composition (``gather_scale`` /
``sampled_matmul``) pads operands to block multiples on the host (zero
rows contribute nothing; padded index slots point at row 0 with scale
0); ``row_norms`` pads rows only.  The fused path
(:func:`fused_sampled_dw`) is ragged-native — nothing is padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.kernel_config import DEFAULT_KERNEL_CONFIG, KernelConfig
from repro.kernels import autotune as _autotune
from repro.kernels import fused_sampling as _fused
from repro.kernels import gather_scale as _gather
from repro.kernels import ref as _ref
from repro.kernels import row_norms as _norms
from repro.kernels import sampled_matmul as _smm
from repro.launch import mesh as mesh_lib


def _resolve(kernel: KernelConfig | None) -> KernelConfig:
    return DEFAULT_KERNEL_CONFIG if kernel is None else kernel


def _per_data_shard(fn, *args, reduce: bool):
    """Run the kernel call ``fn(*args)`` on each data shard.

    The compiler cannot partition a Mosaic kernel, so under an ambient
    mesh of more than one device the call runs inside ``shard_map``: the
    leading (batch or row) dim of every argument is split over the
    mesh's data axes (``launch.mesh.data_axes``) and the other dims are
    whole on each device.  ``reduce``: the output is a sum over the
    leading dim, so the per-shard partials are summed across the data
    axes; otherwise the output keeps the leading split.

    The mesh is the ambient one: a step jitted over sharded arrays must
    run under ``jax.set_mesh`` (``Run.step`` does), or the kernel call
    reaches the partitioner whole and the compiler refuses it.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return fn(*args)
    data = mesh_lib.data_axes(mesh)
    if reduce and data:
        def body(*a):
            return jax.lax.psum(fn(*a), data)
    else:
        body = fn
    spec = P(data or None)
    # check_vma=False: pallas_call outputs carry no varying-axes type
    return jax.shard_map(body, in_specs=(spec,) * len(args),
                         out_specs=P() if reduce else spec,
                         check_vma=False)(*args)


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _pad_cols(x: jax.Array, mult: int) -> jax.Array:
    d = x.shape[1]
    pad = (-d) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)))


def _pad_plan(idx: jax.Array, scale: jax.Array,
              k_padded: int) -> tuple[jax.Array, jax.Array]:
    """Pad (B, k) plan vectors to k_padded slots: idx 0 (in-bounds DMA
    target), scale 0 (contributes nothing)."""
    b, k = idx.shape
    pad = k_padded - k
    idxp = idx.astype(jnp.int32)
    scalep = scale.astype(jnp.float32)
    if pad:
        idxp = jnp.concatenate(
            [idxp, jnp.zeros((b, pad), jnp.int32)], axis=1)
        scalep = jnp.concatenate(
            [scalep, jnp.zeros((b, pad), jnp.float32)], axis=1)
    return idxp, scalep


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
        block_rows = _autotune.sublane_block(n, cfg.block_rows or 256,
                                             x.dtype)
        block_d = _autotune.lane_block(d, cfg.block_d or 512)
        out = _norms.row_norms(_pad_rows(x, block_rows),
                               block_rows=block_rows, block_d=block_d,
                               interpret=cfg.interpreted)
        return out[:n]

    return _per_data_shard(local, x, reduce=False)


@functools.partial(jax.jit, static_argnames=("kernel",))
def gather_scale(x: jax.Array, idx: jax.Array, scale: jax.Array, *,
                 kernel: KernelConfig | None = None) -> jax.Array:
    """(k, d) = x[idx] * scale[:, None] via scalar-prefetch gather."""
    cfg = _resolve(kernel)
    if not cfg.use_pallas:
        return _ref.gather_scale_ref(x, idx, scale)
    block_d = min(cfg.block_d or 512, x.shape[1])
    xp = _pad_cols(x, block_d)
    out = _gather.gather_scale(xp, idx.astype(jnp.int32),
                               scale.astype(jnp.float32),
                               block_d=block_d, interpret=cfg.interpreted)
    return out[:, :x.shape[1]]


def _as_batched(hsub, dz, idx, scale):
    if hsub.ndim == 2:
        return hsub[None], dz[None], idx[None], scale[None]
    return hsub, dz, idx, scale


@functools.partial(jax.jit, static_argnames=("kernel",))
def sampled_matmul(hsub: jax.Array, dz: jax.Array, idx: jax.Array,
                   scale: jax.Array, *,
                   kernel: KernelConfig | None = None) -> jax.Array:
    """dW = sum_b hsub_b^T @ (dz_b[idx_b] * scale_b) — the LEGACY
    even-tiling kernel (host-pads H' and dZ to block multiples).

    Retained as the parity/benchmark reference for
    :func:`fused_sampled_dw`, which does the same contraction without
    the big-operand padding.  Batched form: hsub (B, k, d_in), dz
    (B, n, d_out), idx/scale (B, k); 2-D operands = B == 1.  Returns
    (d_in, d_out) f32.
    """
    cfg = _resolve(kernel)
    hsub, dz, idx, scale = _as_batched(hsub, dz, idx, scale)
    b, k, d_in = hsub.shape
    d_out = dz.shape[2]
    if not cfg.use_pallas:
        return _ref.sampled_matmul_batched_ref(
            hsub, dz, idx.astype(jnp.int32), scale)
    bm, bn, bk = _autotune.resolve_blocks(cfg, d_in, d_out, b, k,
                                          hsub.dtype)
    hp = jax.vmap(lambda h: _pad_cols(_pad_rows(h, bk), bm))(hsub)
    dzp = jax.vmap(_pad_cols, in_axes=(0, None))(dz, bn)
    idxp, scalep = _pad_plan(idx, scale, hp.shape[1])
    out = _smm.sampled_matmul(hp, dzp, idxp, scalep, bm=bm, bn=bn, bk=bk,
                              interpret=cfg.interpreted)
    return out[:d_in, :d_out]


@functools.partial(jax.jit, static_argnames=("kernel",))
def fused_sampled_dw(hsub: jax.Array, dz: jax.Array, idx: jax.Array,
                     scale: jax.Array, *,
                     kernel: KernelConfig | None = None) -> jax.Array:
    """dW = sum_b hsub_b^T @ (dz_b[idx_b] * scale_b) via one XLA
    gather+scale fusion and the ragged-native contraction kernel.

    Same contract as :func:`sampled_matmul`.  A standalone op: the
    training step's sampled linears compute the same contraction with
    XLA's dot_general (``core.linear._sampled_dw``), which a TPU v5e
    runs an order of magnitude faster.  Blocks come from the autotuner's
    tuning table keyed on (d_in, d_out, B, k, dtype) unless the config
    pins them.  Falls back to the jnp oracle when the config says so
    (backend ``jnp``, or ``auto`` on an interpret-mode backend).
    """
    cfg = _resolve(kernel)
    hsub, dz, idx, scale = _as_batched(hsub, dz, idx, scale)
    if not cfg.use_pallas:
        return _ref.sampled_matmul_batched_ref(
            hsub, dz, idx.astype(jnp.int32), scale)

    def local(hsub, dz, idx, scale):
        b, k, d_in = hsub.shape
        bm, bn, bk = _autotune.resolve_blocks(cfg, d_in, dz.shape[2], b, k,
                                              hsub.dtype)
        return _fused.fused_sampled_dw(hsub, dz, idx, scale, bm=bm, bn=bn,
                                       bk=bk, interpret=cfg.interpreted)

    return _per_data_shard(local, hsub, dz, idx, scale, reduce=True)


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
    stay explicit: flash tiling is seq-length geometry, not part of the
    sampled-GEMM tuning table.
    """
    from repro.kernels import flash_attention as _fl
    cfg = _resolve(kernel)
    return _fl.flash_attention_fwd(q, k, v, group=group, causal=causal,
                                   bq=bq, bk=bk, interpret=cfg.interpreted)
