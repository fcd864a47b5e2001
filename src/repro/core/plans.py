"""Column-row sampling plans (Eq. 2-6 of the paper).

A *plan* is a static-shape description of which k column-row pairs of an
m-term contraction participate in the approximated GEMM and with what
scale:

    GEMM(X, Y) = sum_i X[:,i] Y[i,:] ~= sum_t scale_t X[:,idx_t] Y[idx_t,:]

Three plan builders are provided:

  * ``crs_plan``      -- iid sampling from P, scale 1/(k p_i)          (Eq. 5)
  * ``det_topk_plan`` -- top-k by probability, scale 1 (biased;
                         Adelman et al. 2021)
  * ``wtacrs_plan``   -- the paper's Winner-Take-All plan: the |C| largest
                         atoms enter deterministically (scale 1), the
                         remaining k-|C| slots are iid samples from the
                         renormalized tail with scale
                         (1 - sum_C p) / ((k-|C|) p_j)                  (Eq. 6)

|C| is chosen per Theorem 2 to minimize (1 - sum_C p) / (k - |C|).

Everything is shape-static and jit-safe: |C| is a traced integer, realised
via masks over a fixed k slots.

Each builder registers itself in ``repro.core.estimator_registry``;
``build_plan`` (and every other dispatch site) resolves by name through
the registry, so new plan builders can be added from any module without
editing this file.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import estimator_registry as registry

_EPS = 1e-30


class SamplePlan(NamedTuple):
    """Static-shape sampling plan over a contraction dimension of size m.

    A plan is agnostic to WHICH model dimension it sub-samples — token,
    expert-capacity, or flattened rows all look like "a contraction dim
    of size m" here.  The dimension a given linear samples over is
    recorded as tag metadata at trace time (``repro.models.common``
    sampled-dim recording); consumers that assume a particular dim (the
    per-sample znorm cache assumes tokens) must check that metadata
    rather than the plan."""

    idx: jax.Array        # (k,) int32 indices into the contraction dim
    scale: jax.Array      # (k,) f32 per-slot scale factors
    # Diagnostics (scalars), useful for tests/benchmarks.
    c_size: jax.Array     # |C|: number of deterministic slots (0 for CRS)
    det_mass: jax.Array   # sum_{c in C} p_c


def column_row_probabilities(x_col_norms: jax.Array,
                             y_row_norms: jax.Array) -> jax.Array:
    """Optimal CRS distribution (Eq. 3): p_i ∝ ||X_:,i|| * ||Y_i,:||."""
    w = x_col_norms * y_row_norms
    total = jnp.sum(w)
    # Guard: if everything is zero fall back to uniform (still unbiased).
    m = w.shape[0]
    uniform = jnp.full((m,), 1.0 / m, dtype=w.dtype)
    p = jnp.where(total > 0, w / jnp.maximum(total, _EPS), uniform)
    return p


def crs_plan(p: jax.Array, k: int, key: jax.Array) -> SamplePlan:
    """iid column-row sampling (Eq. 5). Unbiased."""
    logits = jnp.log(jnp.maximum(p, _EPS))
    idx = jax.random.categorical(key, logits, shape=(k,))
    scale = 1.0 / (k * jnp.maximum(p[idx], _EPS))
    zero = jnp.zeros((), dtype=p.dtype)
    return SamplePlan(idx.astype(jnp.int32), scale.astype(p.dtype),
                      jnp.zeros((), jnp.int32), zero)


def det_topk_plan(p: jax.Array, k: int) -> SamplePlan:
    """Deterministic top-k selection without scaling (Adelman et al.).

    This estimator is *biased*: it simply drops the tail mass.  Included as
    the paper's ablation baseline ("Deterministic" in Fig. 8).
    """
    _, idx = jax.lax.top_k(p, k)
    scale = jnp.ones((k,), dtype=p.dtype)
    det_mass = jnp.sum(p[idx])
    return SamplePlan(idx.astype(jnp.int32), scale,
                      jnp.asarray(k, jnp.int32), det_mass)


def optimal_c_size(p_sorted_cumsum: jax.Array, k: int,
                   cap: float = 1.0) -> jax.Array:
    """Theorem 2: |C|* = argmin_{c in 0..k-1} (1 - sum_topc p) / (k - c).

    ``p_sorted_cumsum`` is the cumulative sum of descending-sorted
    probabilities.  Returns a traced int32 scalar in [0, k-1] (we keep at
    least one stochastic slot so the estimator stays well-defined and
    unbiased even when the distribution is fully concentrated; with zero
    residual mass the stochastic term contributes ~0 anyway).
    """
    cs = jnp.arange(k)
    # mass of the top-c atoms, for c = 0..k-1  (c=0 -> 0 mass)
    top_mass = jnp.where(cs == 0, 0.0,
                         p_sorted_cumsum[jnp.maximum(cs - 1, 0)])
    score = (1.0 - top_mass) / (k - cs).astype(p_sorted_cumsum.dtype)
    c_max = int(max(0, min(k - 1, round(cap * k))))
    score = jnp.where(cs <= c_max, score, jnp.inf)
    return jnp.argmin(score).astype(jnp.int32)


def wtacrs_plan(p: jax.Array, k: int, key: jax.Array,
                deterministic_fraction_cap: float = 1.0) -> SamplePlan:
    """Winner-Take-All column-row plan (Eq. 6).  Unbiased, lower variance
    than CRS whenever sum_C p_c > |C|/k (Theorem 2).
    """
    m = p.shape[0]
    order = jnp.argsort(-p)                       # descending
    p_sorted = p[order]
    csum = jnp.cumsum(p_sorted)
    c_star = optimal_c_size(csum, k, cap=deterministic_fraction_cap)
    det_mass = jnp.where(c_star == 0, 0.0, csum[jnp.maximum(c_star - 1, 0)])
    resid = jnp.maximum(1.0 - det_mass, 0.0)

    # rank[i] = position of index i in the descending order
    ranks = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    tail = ranks >= c_star
    logits = jnp.where(tail, jnp.log(jnp.maximum(p, _EPS)), -jnp.inf)
    sampled = jax.random.categorical(key, logits, shape=(k,)).astype(jnp.int32)

    slots = jnp.arange(k, dtype=jnp.int32)
    det_slot = slots < c_star
    idx = jnp.where(det_slot, order[jnp.minimum(slots, m - 1)], sampled)

    n_stoc = jnp.maximum(k - c_star, 1).astype(p.dtype)
    stoc_scale = resid / (n_stoc * jnp.maximum(p[sampled], _EPS))
    scale = jnp.where(det_slot, jnp.ones((), p.dtype), stoc_scale)
    return SamplePlan(idx.astype(jnp.int32), scale.astype(p.dtype),
                      c_star, det_mass.astype(p.dtype))


# ---------------------------------------------------------------------------
# Registry entries + dispatch
# ---------------------------------------------------------------------------

@registry.register_estimator("crs", needs_key=True, biased=False)
def _crs_builder(p, k, key, cfg=None) -> SamplePlan:
    return crs_plan(p, k, key)


@registry.register_estimator("det_topk", needs_key=False, biased=True)
def _det_topk_builder(p, k, key, cfg=None) -> SamplePlan:
    return det_topk_plan(p, k)


@registry.register_estimator("wta_crs", needs_key=True, biased=False)
def _wtacrs_builder(p, k, key, cfg=None) -> SamplePlan:
    cap = 1.0 if cfg is None else cfg.deterministic_fraction_cap
    return wtacrs_plan(p, k, key, cap)


def batched_row_weights(h: jax.Array, znorm: Optional[jax.Array],
                        cfg) -> jax.Array:
    """Unnormalized sampling weights over rows: h (B, S, D) -> (B, S).

    The ||H_b,s|| factor of Eq. 3, times the cached gradient-norm term
    when ``cfg.norm_source == CACHED_GRAD`` (the config is
    authoritative — under ACTIVATION_ONLY a supplied znorm is ignored).
    The row norms run through the Pallas reduction kernel whenever
    ``cfg.kernel`` routes to Pallas; the fallback is an f32-accumulating
    einsum (no materialized f32 copy of h).
    """
    from repro.core.config import NormSource
    kernel = getattr(cfg, "kernel", None)
    if kernel is not None and kernel.use_pallas:
        from repro.kernels import ops as kernel_ops
        flat = h.reshape((-1, h.shape[-1]))
        h_norms = kernel_ops.row_norms(flat, kernel=kernel)
        h_norms = h_norms.reshape(h.shape[:-1])
    else:
        sq = jnp.einsum("...d,...d->...", h, h,
                        preferred_element_type=jnp.float32)
        h_norms = jnp.sqrt(sq)
    if znorm is not None and cfg.norm_source == NormSource.CACHED_GRAD:
        return h_norms * znorm.astype(jnp.float32)
    return h_norms


def build_batched_plans(p: jax.Array, k: int, key_data, cfg) -> SamplePlan:
    """Vmapped per-sample plan building: p (B, m) -> SamplePlan with
    (B, k) idx/scale leaves, one independent plan per batch element.

    ``key_data`` is raw PRNG key data (``jax.random.key_data``) so the
    caller can thread it through a custom_vjp; it is split into one key
    per sample for estimators that need randomness.  The (B, k) idx and
    scale are what the forward's row gather of H' and the backward's
    sampled dW (``core.linear._sampled_dw``) index with.
    """
    b = p.shape[0]
    spec = registry.get_estimator(cfg.kind)
    if spec.needs_key:
        key = jax.random.wrap_key_data(key_data)
        keys = jax.random.split(key, b)
        return jax.vmap(lambda pr, kk: spec.build(pr, k, kk, cfg))(p, keys)
    return jax.vmap(lambda pr: spec.build(pr, k, None, cfg))(p)


def build_plan(kind, p: jax.Array, k: int, key: Optional[jax.Array],
               deterministic_fraction_cap: float = 1.0,
               cfg=None) -> SamplePlan:
    """Dispatch by estimator name through the registry.

    ``kind`` is an EstimatorKind or any registered name; ``cfg`` (optional)
    is forwarded to the builder so custom estimators can read their knobs.
    When ``cfg`` is omitted a minimal one carrying
    ``deterministic_fraction_cap`` is synthesized for backward
    compatibility with the original signature.
    """
    if registry.is_exact(kind):
        raise ValueError(f"no sampling plan for estimator kind {kind}")
    spec = registry.get_estimator(kind)
    if cfg is None:
        from repro.core.config import WTACRSConfig
        cfg = WTACRSConfig(kind=registry.kind_name(kind),
                           deterministic_fraction_cap=
                           deterministic_fraction_cap)
    return spec.build(p, k, key if spec.needs_key else None, cfg)
