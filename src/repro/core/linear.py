"""WTA-CRS linear layer: exact forward, sub-sampled weight-gradient backward.

This implements the paper's core mechanism (Sec. 3.2, Algorithm 1):

    forward:   Z = H @ W                         (exact -> unbiased network)
    backward:  dH = dZ @ W^T                     (exact)
               dW = H'^T @ (dZ[idx] * scale)     (WTA-CRS estimate of H^T dZ)

Only the sub-sampled H' (k rows of H), the k indices and the k scales are
kept as residuals for the backward pass, instead of the full H.  This is
where the activation-memory reduction comes from: for budget k = 0.3 n the
per-linear stored activation shrinks 3.3x.

Distribution design: sampling is PER-SAMPLE — each batch element draws
its own k = budget*S column-row pairs over its S token rows.  The
contraction sum decomposes over batch elements, each estimated
unbiasedly, so the total stays unbiased; and because every op is
elementwise in the batch dimension, data-parallel sharding keeps the
whole plan+gather shard-local (a global top-|C| over the B*S dim would
force an all-gather of the activations on every linear — measured 1.7
TB/device in the 16x16 dry-run).  The paper's own cache granularity is
also per-sample (Algorithm 1), so this is the faithful SPMD expression
of it.

The column-row distribution (Eq. 3) is p_i ∝ ||H_i,:|| * ||dZ_i,:||.  dZ
is unknown at forward time, so the caller may supply ``znorm`` — cached
per-token gradient-norm estimates from the previous step (Algorithm 1's
Cache).  The cached term enters the probabilities only when
``cfg.norm_source == NormSource.CACHED_GRAD``; with ``ACTIVATION_ONLY``
the supplied znorm is ignored for sampling (p_i ∝ ||H_i,:||) but the
*gradient-norm tap* still flows: the cotangent returned for ``znorm`` is
the SQUARED per-token norm of dZ rather than a true derivative (sampling
probabilities are treated as non-differentiable, exactly as in the
paper).  Training code reads grads-of-znorm to refresh the cache
(repro.train.znorm) — including during an activation-only warmup.

Estimator dispatch is by name through ``repro.core.estimator_registry``:
``cfg.kind`` may be any registered estimator, and all public entry
points (``wtacrs_linear``, ``wtacrs_linear_shared``, ``lora_linear``)
are thin wrappers over one internal ``_dispatch_sampled_dense`` path.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from repro import spans
from repro.core import estimator_registry as registry
from repro.core import plans
from repro.core.config import WTACRSConfig

_EPS = 1e-30


# ---------------------------------------------------------------------------
# custom_vjp core: batched (B, S, D) x (D, E), per-sample plans
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sampled_linear(h: jax.Array, w: jax.Array, key_data: jax.Array,
                    znorm: jax.Array, cfg: WTACRSConfig) -> jax.Array:
    return jnp.einsum("bsd,de->bse", h, w)


def _make_plans(h, znorm, key_data, cfg: WTACRSConfig, k: int):
    """Per-sample plans.  h: (B,S,D), znorm: (B,S) -> idx/scale (B,k).

    Dispatches to the registered plan builder for ``cfg.kind``.  The
    znorm term enters the probabilities only under CACHED_GRAD (the
    config is authoritative; see NormSource).  The row-norm pass runs
    through ``plans.batched_row_weights``, the one step of a sampled
    linear that ``cfg.kernel`` routes (Pallas row_norms kernel when the
    config routes to Pallas).
    """
    weights = plans.batched_row_weights(h, znorm, cfg)        # (B, S)
    totals = jnp.sum(weights, axis=-1, keepdims=True)
    uniform = jnp.full_like(weights, 1.0 / weights.shape[-1])
    p = jnp.where(totals > 0, weights / jnp.maximum(totals, _EPS), uniform)

    plan = plans.build_batched_plans(p, k, key_data, cfg)
    return plan.idx, plan.scale


def _rowgather(x: jax.Array, idx: jax.Array) -> jax.Array:
    """(B, S, D)[B, k] -> (B, k, D) without broadcasting an index tensor
    to the output shape (take_along_axis materializes u32[B,k,D])."""
    return jax.vmap(lambda xb, ib: jnp.take(xb, ib, axis=0))(x, idx)


def gather_scaled(dz: jax.Array, idx: jax.Array,
                  scale: jax.Array) -> jax.Array:
    """(B, n, d_out)[B, k] * scale -> (B, k, d_out) in dz's dtype: the
    scale is applied in f32 and rounded once, so the dW contraction
    runs on dZ's dtype (bf16 at the MXU's native rate)."""
    rows = jax.vmap(lambda d, i: jnp.take(d, i, axis=0))(dz, idx)
    return (rows.astype(jnp.float32)
            * scale.astype(jnp.float32)[:, :, None]).astype(dz.dtype)


def _sampled_dw(h_sub, dz, idx, scale, out_dtype):
    """dW = sum_b H'_b^T @ (dZ_b[idx_b] * scale_b): the k rows of dZ
    gathered and scaled (``gather_scaled``), then one dot_general
    contracting (B, k) with f32 accumulation, under the ``wtacrs_dw``
    scope.  This is the sampled dW on every ``KernelConfig`` backend
    (the config routes only the plan's row norms); under a mesh the
    partitioner shards it over the data axes and adds the cross-shard
    sum itself."""
    with jax.named_scope(spans.WTACRS_DW):
        dz_sub = gather_scaled(dz, idx, scale)                 # (B, k, E)
        dw = jax.lax.dot_general(
            h_sub, dz_sub, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw.astype(out_dtype)


def _sq_norm_tap(dz):
    # Gradient-norm tap: NOT a derivative (see module doc).  Squared norms
    # so per-sample caches broadcast over positions sum correctly.
    return jnp.einsum("bse,bse->bs", dz, dz,
                      preferred_element_type=jnp.float32)      # (B, S)


def _sampled_linear_fwd(h, w, key_data, znorm, cfg: WTACRSConfig):
    z = jnp.einsum("bsd,de->bse", h, w)
    k = cfg.budget_rows(h.shape[1])
    with jax.named_scope(spans.WTACRS_PLAN):
        idx, scale = _make_plans(h, znorm, key_data, cfg, k)
        h_sub = _rowgather(h, idx)                            # (B, k, D)
    # Name the kept tensors so remat policies can save exactly these.
    h_sub = checkpoint_name(h_sub, "wtacrs_saved")
    idx = checkpoint_name(idx, "wtacrs_saved")
    scale = checkpoint_name(scale, "wtacrs_saved")
    return z, (h_sub, idx, scale, w, key_data.shape)


def _sampled_linear_bwd(cfg: WTACRSConfig, residuals, dz):
    h_sub, idx, scale, w, key_shape = residuals
    dh = jnp.einsum("bse,de->bsd", dz, w)
    dw = _sampled_dw(h_sub, dz, idx, scale, w.dtype)
    tap = _sq_norm_tap(dz)
    dkey = np.zeros(key_shape, dtype=jax.dtypes.float0)
    return dh.astype(h_sub.dtype), dw, dkey, tap


_sampled_linear.defvjp(_sampled_linear_fwd, _sampled_linear_bwd)


# ---------------------------------------------------------------------------
# Shared-plan variant: several weights consuming the SAME activation
# (q/k/v, SwiGLU wi/wg, expert wi/wg) share one plan and ONE stored H'.
# Beyond-paper memory optimization: the paper stores a sub-sampled copy
# per op; sharing cuts attention-input residuals 3x and gated-MLP 2x at
# identical unbiasedness (each dW_i is the Eq. 6 estimator under the
# same, valid plan; only the variance coupling across the three
# estimates changes, not any mean).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _sampled_linear_shared(h, ws, key_data, znorm, cfg: WTACRSConfig):
    return tuple(jnp.einsum("bsd,de->bse", h, w) for w in ws)


def _sampled_linear_shared_fwd(h, ws, key_data, znorm, cfg: WTACRSConfig):
    zs = tuple(jnp.einsum("bsd,de->bse", h, w) for w in ws)
    k = cfg.budget_rows(h.shape[1])
    with jax.named_scope(spans.WTACRS_PLAN):
        idx, scale = _make_plans(h, znorm, key_data, cfg, k)
        h_sub = _rowgather(h, idx)
    h_sub = checkpoint_name(h_sub, "wtacrs_saved")
    idx = checkpoint_name(idx, "wtacrs_saved")
    scale = checkpoint_name(scale, "wtacrs_saved")
    return zs, (h_sub, idx, scale, ws, key_data.shape)


def _sampled_linear_shared_bwd(cfg: WTACRSConfig, residuals, dzs):
    h_sub, idx, scale, ws, key_shape = residuals
    dh = sum(jnp.einsum("bse,de->bsd", dz, w)
             for dz, w in zip(dzs, ws))
    dws = []
    tap = None
    for dz in dzs:
        dws.append(_sampled_dw(h_sub, dz, idx, scale, ws[0].dtype))
        t = _sq_norm_tap(dz)
        tap = t if tap is None else tap + t
    dkey = np.zeros(key_shape, dtype=jax.dtypes.float0)
    return dh.astype(h_sub.dtype), tuple(dws), dkey, tap


_sampled_linear_shared.defvjp(_sampled_linear_shared_fwd,
                              _sampled_linear_shared_bwd)


# ---------------------------------------------------------------------------
# Unified internal dispatch + thin public wrappers
# ---------------------------------------------------------------------------

def _dispatch_sampled_dense(h: jax.Array, ws: Sequence[jax.Array],
                            key: Optional[jax.Array],
                            znorm: Optional[jax.Array],
                            cfg: WTACRSConfig,
                            biases: Optional[Sequence] = None,
                            shared: bool = False) -> Tuple[jax.Array, ...]:
    """The single sampled-dense path every public wrapper routes through.

    Handles: leading-dim reshape to (B, S, D), the exact short-circuit
    (EXACT kind or budget covering all rows), znorm normalization, key
    requirements from the registered estimator's signature, and the
    shared-plan vs per-weight choice.  Returns one output per weight.
    """
    lead = h.shape[:-1]
    squeeze = h.ndim == 2
    h3 = h[None] if squeeze else h.reshape((-1,) + h.shape[-2:])
    b, s = h3.shape[0], h3.shape[1]

    if cfg.is_exact or cfg.budget_rows(s) >= s:
        zs = tuple(jnp.einsum("...sd,de->...se", h, w) for w in ws)
    else:
        spec = registry.get_estimator(cfg.kind)
        if key is None:
            if spec.needs_key:
                raise ValueError(
                    f"estimator {cfg.kind_name!r} requires a PRNG key")
            key = jax.random.PRNGKey(0)     # keyless builder: value unused
        zn = (jnp.ones((b, s), jnp.float32) if znorm is None
              else znorm.reshape((b, s)).astype(jnp.float32))
        key_data = jax.random.key_data(key)
        if shared and len(ws) > 1:
            if not spec.supports_shared:
                raise ValueError(f"estimator {cfg.kind_name!r} does not "
                                 f"support shared plans")
            z3s = _sampled_linear_shared(h3, tuple(ws), key_data, zn, cfg)
        else:
            z3s = tuple(_sampled_linear(h3, w, key_data, zn, cfg)
                        for w in ws)
        zs = tuple(z[0] if squeeze else z.reshape(lead + (z.shape[-1],))
                   for z in z3s)

    if biases is not None:
        zs = tuple(z if bias is None else z + bias
                   for z, bias in zip(zs, biases))
    return zs


def wtacrs_linear(h: jax.Array, w: jax.Array,
                  key: Optional[jax.Array] = None,
                  znorm: Optional[jax.Array] = None,
                  cfg: WTACRSConfig = WTACRSConfig(),
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Linear layer with estimator-approximated weight gradient.

    Args:
      h: activations (..., S, d_in); sampling happens over S per leading
        index.  2-D inputs (n, d_in) are treated as one sample of n rows.
      w: weight (d_in, d_out).
      key: PRNG key for the sampling plans (not needed for estimators
        whose registry entry declares ``needs_key=False``, e.g.
        EXACT/DET_TOPK).
      znorm: gradient-norm estimates, shape h.shape[:-1] (or broadcastable
        per-sample values); consulted for sampling only under
        ``NormSource.CACHED_GRAD``, but the gradient-norm tap always
        flows back through this argument.
      cfg: estimator configuration (``cfg.kind`` may be any registered
        estimator name).
      bias: optional (d_out,), added exactly.
    """
    return _dispatch_sampled_dense(h, (w,), key, znorm, cfg,
                                   biases=(bias,))[0]


def wtacrs_linear_shared(h: jax.Array, ws, key=None, znorm=None,
                         cfg: WTACRSConfig = WTACRSConfig(),
                         biases=None):
    """Shared-plan multi-linear: returns one output per weight in ``ws``.

    h: (..., S, d_in); every w: (d_in, d_out_i).  One plan and ONE stored
    H' serve all weights (see the shared-plan notes above)."""
    return _dispatch_sampled_dense(h, tuple(ws), key, znorm, cfg,
                                   biases=biases, shared=True)


def read_grad_norm_tap(grads_znorm: jax.Array) -> jax.Array:
    """Convert tap cotangents (squared norms) into gradient norms."""
    return jnp.sqrt(jnp.maximum(grads_znorm, 0.0))
