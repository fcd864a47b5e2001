"""MLP variants, the sort-based capacity MoE layer, and the expert-share
layer (``apply_moe_share``, below).

The MoE dispatch is the scalable sort/scatter formulation (no (T, E, C)
one-hot): tokens are ranked within their routed expert via a bincount-
offset trick, dropped beyond capacity, gathered into (E, C, D) slots,
run through the expert FFNs as one batched einsum (expert dim shards over
the "model"/EP mesh axis), and combined back with scatter-add weighted by
the router probabilities.  Everything is static-shape and differentiable.

Expert GEMMs are WTA-CRS'd per expert (vmapped custom_vjp) when the
policy enables it: the contraction (capacity) dimension is sub-sampled
exactly like the token dimension of a dense linear.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import estimator_registry as est_registry
from repro.core.linear import wtacrs_linear
from repro.models import common as cm


def act_fn(kind: str):
    if kind == "swiglu":
        return None  # handled structurally (gated)
    if kind == "gelu":
        return jax.nn.gelu
    if kind == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(kind)


def init_mlp(cfg, key, dtype, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": cm.dense_init(ks[0], (d, f), ("embed", "mlp"), dtype)}
    if cfg.mlp_type == "swiglu":
        p["wg"] = cm.dense_init(ks[1], (d, f), ("embed", "mlp"), dtype)
    p["wo"] = cm.dense_init(ks[2], (f, d), ("mlp", "embed"), dtype)
    return p


def apply_mlp(cfg, p, ctx: cm.Ctx, h):
    if cfg.mlp_type == "swiglu":
        # shared plan + single stored H' for wi/wg (same input)
        up, gate = ctx.linear_shared(("mlp_wi", "mlp_wg"), h,
                                     [p["wi"], p["wg"]])
        z = jax.nn.silu(gate) * up
    else:
        up = ctx.linear("mlp_wi", h, p["wi"])
        z = act_fn(cfg.mlp_type)(up)
    return ctx.linear("mlp_wo", z, p["wo"])


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

def init_moe(cfg, key, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": cm.dense_init(ks[0], (d, e), ("embed", None), dtype,
                                scale=0.02),
        "wi": cm.dense_init(ks[1], (e, d, f), ("experts", "embed", "mlp"),
                            dtype),
        "wg": cm.dense_init(ks[2], (e, d, f), ("experts", "embed", "mlp"),
                            dtype),
        "wo": cm.dense_init(ks[3], (e, f, d), ("experts", "mlp", "embed"),
                            dtype),
    }


def moe_capacity(cfg, n_tokens: int) -> int:
    cap = int(cfg.capacity_factor * cfg.moe_top_k * n_tokens
              // cfg.n_experts)
    return max(cap, 1)


def _expert_ffn(cfg, p, ctx: cm.Ctx, xs: jax.Array) -> jax.Array:
    """xs: (E, C, D) -> (E, C, D), optionally WTA-CRS'd per expert.

    The estimator config resolves per tag (``<prefix>moe_expert``) like
    any dense linear, so rules can keep experts exact while sampling the
    dense blocks or vice versa."""
    cfg_w = ctx.policy.config_for(ctx.tag_prefix + "moe_expert")
    wtacrs_on = not cfg_w.is_exact and ctx.key is not None
    if wtacrs_on:
        e, cap, d = xs.shape
        keys = jax.random.split(ctx._key_for("moe_expert"), e)
        # group-wise sampling: plans stay local to capacity shards
        g = ctx.policy.moe_groups if cap % ctx.policy.moe_groups == 0 else 1

        def one(x, wi, wg, wo, k):
            k1, k3 = jax.random.split(k, 2)
            xg = x.reshape(g, cap // g, d)
            # shared plan across wi/wg (same expert input)
            from repro.core.linear import wtacrs_linear_shared
            up, gate = wtacrs_linear_shared(
                xg, (wi.astype(x.dtype), wg.astype(x.dtype)), key=k1,
                cfg=cfg_w)
            z = jax.nn.silu(gate) * up
            out = wtacrs_linear(z, wo.astype(x.dtype), key=k3, cfg=cfg_w)
            return out.reshape(cap, d)

        return jax.vmap(one)(xs, p["wi"], p["wg"], p["wo"], keys)
    up = jnp.einsum("ecd,edf->ecf", xs, p["wi"].astype(xs.dtype))
    gate = jnp.einsum("ecd,edf->ecf", xs, p["wg"].astype(xs.dtype))
    z = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", z, p["wo"].astype(xs.dtype))


def _dispatch_group(e: int, k: int, cap: int, x, top_p, top_e):
    """Capacity-dispatch of one token group.  x: (Tg, D); returns
    (xs (E, C, D), tok_of_slot, w_of_slot, occupied, keep)."""
    t = x.shape[0]
    flat_e = top_e.reshape(-1)                                 # (Tg*k,)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * k) - starts[sorted_e]
    keep = rank < cap
    # over-capacity entries get an out-of-bounds slot and are dropped
    slot = jnp.where(keep, sorted_e * cap + rank, e * cap)

    tok_of_slot = jnp.zeros((e * cap,), jnp.int32).at[slot].set(
        flat_tok[order], mode="drop")
    w_of_slot = jnp.zeros((e * cap,), jnp.float32).at[slot].set(
        flat_p[order], mode="drop")
    occupied = jnp.zeros((e * cap,), jnp.bool_).at[slot].set(
        True, mode="drop")
    xs = jnp.take(x, tok_of_slot, axis=0)
    xs = jnp.where(occupied[:, None], xs, 0).reshape(e, cap, x.shape[1])
    return xs, tok_of_slot, w_of_slot, occupied, keep


def apply_moe(cfg, p, ctx: cm.Ctx, h) -> Tuple[jax.Array, Dict]:
    """h: (B, S, D) -> (B, S, D), plus aux losses/stats.

    Dispatch is GROUP-LOCAL (GShard-style): tokens are split into
    ``policy.moe_groups`` groups (== data shards) that each rank/drop
    against a per-group capacity, so the gather/scatter never crosses a
    shard; the only cross-device movement is the (E <-> tokens)
    resharding of the compact (G, E, C, D) dispatch tensor — a clean
    all-to-all instead of an activation all-gather (EXPERIMENTS §Perf).
    """
    b, s, d = h.shape
    t = b * s
    e, k = cfg.n_experts, cfg.moe_top_k
    g = ctx.policy.moe_groups if (s > 1 and t % ctx.policy.moe_groups == 0
                                  ) else 1
    # decode (s == 1): capacity = t guarantees no drops, so cached decode
    # matches teacher-forced forward exactly
    cap = moe_capacity(cfg, t // g) if s > 1 else t
    x = h.reshape(t, d)

    logits = ctx.linear("moe_router", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    top_p, top_e = jax.lax.top_k(probs, k)                     # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)     # renormalize

    xg = x.reshape(g, t // g, d)
    pg = top_p.reshape(g, t // g, k)
    eg = top_e.reshape(g, t // g, k)
    xs, tok_of_slot, w_of_slot, occupied, keep = jax.vmap(
        lambda xx, pp, ee: _dispatch_group(e, k, cap, xx, pp, ee))(
        xg, pg, eg)                                            # (G, E, C, D)

    xs = jnp.swapaxes(xs, 0, 1)                                # (E, G, C, D)
    if ctx.policy.moe_pspec is not None:
        from jax.sharding import PartitionSpec as _P
        e_ax, cap_ax = ctx.policy.moe_pspec
        xs = jax.lax.with_sharding_constraint(
            xs, _P(e_ax, cap_ax, None, None))
    ys = _expert_ffn(cfg, p, ctx, xs.reshape(e, g * cap, d))
    ys = ys.reshape(e, g, cap, d)
    if ctx.policy.moe_pspec is not None:
        from jax.sharding import PartitionSpec as _P
        e_ax, cap_ax = ctx.policy.moe_pspec
        ys = jax.lax.with_sharding_constraint(
            ys, _P(e_ax, cap_ax, None, None))
    ys = jnp.swapaxes(ys, 0, 1).reshape(g, e * cap, d)         # (G, E*C, D)

    def _combine(y_g, w_g, occ_g, tok_g):
        y_g = y_g * w_g[:, None].astype(y_g.dtype)
        return jnp.zeros((t // g, d), y_g.dtype).at[tok_g].add(
            jnp.where(occ_g[:, None], y_g, 0))

    out = jax.vmap(_combine)(ys, w_of_slot, occupied, tok_of_slot)
    out = out.reshape(t, d)

    # aux: load-balancing loss (Switch-style) + drop fraction
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e), axis=0)
    aux = {"lb_loss": e * jnp.sum(me * ce),
           "drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert share: sigmoid routing over every expert, this chip's experts run
# without capacity (DeepSeek-V3, arXiv:2412.19437)
# ---------------------------------------------------------------------------

def init_moe_share(cfg, key, dtype):
    """Router over all ``cfg.n_routed`` experts (f32, with its frozen
    selection bias), the ``cfg.n_experts`` experts held here, and the
    shared experts as one SwiGLU of ``n_shared_experts * moe_d_ff``."""
    d, f, e, r = cfg.d_model, cfg.expert_d_ff, cfg.n_experts, cfg.n_routed
    ks = jax.random.split(key, 6)
    p = {
        "router": cm.dense_init(ks[0], (d, r), ("embed", None),
                                jnp.float32, scale=0.02),
        # e_score_correction_bias: published values ship with weights
        "router_bias": cm.dense_init(ks[1], (r,), (None,), jnp.float32,
                                     scale=1e-2),
        "wi": cm.dense_init(ks[2], (e, d, f), ("experts", "embed", "mlp"),
                            dtype, scale=d ** -0.5),
        "wg": cm.dense_init(ks[3], (e, d, f), ("experts", "embed", "mlp"),
                            dtype, scale=d ** -0.5),
        "wo": cm.dense_init(ks[4], (e, f, d), ("experts", "mlp", "embed"),
                            dtype, scale=f ** -0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(cfg, ks[5], dtype,
                               d_ff=cfg.n_shared_experts * f)
    return p


def route_sigmoid(cfg, p, x):
    """x (T, D) -> (top_e (T, K) global expert ids, w (T, K) f32).

    s = sigmoid(x W_r) in f32; the top-K experts by s + b, with b the
    frozen selection bias (no gradient reaches it); weights s over their
    sum, times ``routed_scaling``."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    bias = jax.lax.stop_gradient(p["router_bias"]).astype(jnp.float32)
    _, top_e = jax.lax.top_k(s + bias, cfg.moe_top_k)
    top_s = jnp.take_along_axis(s, top_e, axis=-1)
    w = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    return top_e, w * cfg.routed_scaling


def dispatch_share(cfg, top_e):
    """Rows of this share's experts, sorted by expert, with no capacity.

    Of the T*K (token, expert) pairs, those whose expert is held here are
    sorted to the front by local expert (token order within an expert);
    the buffer keeps the first M = T * min(K, n_experts) (the most that
    can be held), so no pair is ever dropped.  Returns (tok (M,) token of
    each row, group_sizes (E,) rows per held expert, pos (T, K) buffer
    row of each pair, M where its expert is not held)."""
    t, kk = top_e.shape
    e = cfg.n_experts
    local = top_e.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < e)
    sort_key = jnp.where(held, local, e)
    order = jnp.argsort(sort_key, stable=True)
    m = t * min(kk, e)
    sizes = jnp.bincount(sort_key, length=e + 1)[:e].astype(jnp.int32)
    pos = jnp.zeros((t * kk,), jnp.int32).at[order].set(
        jnp.arange(t * kk, dtype=jnp.int32))
    pos = jnp.where(held, pos, m).reshape(t, kk)
    return (order[:m] // kk).astype(jnp.int32), sizes, pos


def _zero_past_groups(a, sizes):
    """``a`` (M, ...) with the rows past the groups' end set to zero.

    ``jax.lax.ragged_dot`` leaves its result there unspecified (CPU
    writes zeros, the TPU need not), and each padding row of the
    dispatch buffer is gathered from a real token, so an unspecified row
    that reached a gradient would land on that token."""
    valid = jnp.arange(a.shape[0]) < jnp.sum(sizes)
    return jnp.where(valid.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0)


def _grouped(x, w, sizes):
    """``ragged_dot`` with the rows past the groups zero in its result
    and in its input's gradient."""
    return _zero_past_groups(
        jax.lax.ragged_dot(_zero_past_groups(x, sizes), w, sizes), sizes)


@jax.custom_vjp
def _expert_linears(x, ws, sizes, sel, scale):
    """Grouped GEMMs x @ w[e] over each expert's rows; each dW[e] a
    WTA-CRS estimate from the rows ``sel[e]`` (k of them) with
    ``scale[e]``, shared by every w in ``ws`` (they read the same x).
    Rows past the groups are zero in the result and in dx."""
    return tuple(_zero_past_groups(jax.lax.ragged_dot(x, w, sizes), sizes)
                 for w in ws)


def _expert_linears_fwd(x, ws, sizes, sel, scale):
    with jax.named_scope(spans.MOE_EXPERTS):
        outs = tuple(_zero_past_groups(jax.lax.ragged_dot(x, w, sizes),
                                       sizes) for w in ws)
    with jax.named_scope(spans.WTACRS_PLAN):
        h_sub = jnp.take(x, sel, axis=0)                    # (E, k, D)
    return outs, (h_sub, sel, scale, ws, sizes)


def _expert_linears_bwd(res, dys):
    h_sub, sel, scale, ws, sizes = res
    with jax.named_scope(spans.MOE_EXPERTS):
        dx = _zero_past_groups(sum(
            jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2), sizes)
            for dy, w in zip(dys, ws)), sizes)
        with jax.named_scope(spans.WTACRS_DW):
            dws = tuple(jnp.einsum(
                "ekd,ekf->edf", h_sub,
                jnp.take(dy, sel, axis=0) * scale[..., None].astype(
                    dy.dtype), preferred_element_type=jnp.float32
            ).astype(w.dtype) for dy, w in zip(dys, ws))
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (dx.astype(h_sub.dtype), dws, zero(sizes), zero(sel),
            jnp.zeros_like(scale))


_expert_linears.defvjp(_expert_linears_fwd, _expert_linears_bwd)


def _expert_plans(wcfg, norms, routed, row_of, keys, k):
    """Per held expert, a plan of k of the T token rows, each routed
    token's probability proportional to ``norms`` ((T,) or (E, T)),
    others zero.  Returns (buffer rows (E, k), scale (E, k)); a slot on
    a row of zero probability carries scale 0."""
    p = norms * routed                                       # (E, T)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    spec = est_registry.get_estimator(wcfg.kind)
    plan = jax.vmap(lambda pr, kk: spec.build(pr, k, kk, wcfg))(p, keys)
    live = jnp.take_along_axis(p, plan.idx, axis=1) > 0
    sel = jnp.take_along_axis(row_of, plan.idx, axis=1)
    return sel, jnp.where(live, plan.scale, 0.0).astype(jnp.float32)


def _row_norms(x):
    """Sampling weights: no gradient flows into a plan."""
    x = jax.lax.stop_gradient(x)
    return jnp.sqrt(jnp.einsum("...d,...d->...", x, x,
                               preferred_element_type=jnp.float32))


def _expert_ffn_share(cfg, p, ctx: cm.Ctx, x, top_e, tok, sizes, pos):
    """The held experts' SwiGLU over their routed rows: x (T, D) ->
    rows (M, D) in buffer order (rows past the experts' sum are 0).

    WTA-CRS key convention (the plain reference mirrors it): with the
    layer's key ``ctx.key`` and its tag prefix, the up/gate plan of the
    expert with global id g uses fold_in(fold_in(ctx.key,
    crc32(prefix+"moe_wi+"+prefix+"moe_wg")), g) and the down plan
    fold_in(fold_in(ctx.key, crc32(prefix+"moe_wo")), g).  Each plan
    draws k = budget_rows(T * K / n_routed) (the expected load) of the T
    token rows, a row's probability proportional to its activation's
    norm where the token is routed to g and zero elsewhere, so each
    expert's dW estimate is unbiased over that expert's rows alone.  The
    estimator config is that of ``moe_wi`` (up and gate) and of
    ``moe_wo`` (down); unless both sample, both are exact."""
    t, kk = top_e.shape
    e, g0 = cfg.n_experts, cfg.expert_offset
    tags = [ctx.tag_prefix + n for n in ("moe_wi", "moe_wg", "moe_wo")]
    for tag in tags:
        ctx._record_tag(tag, cm.SAMPLED_DIM_ROWS)
    ws = [p[n].astype(ctx.compute_dtype or p[n].dtype)
          for n in ("wi", "wg", "wo")]
    with jax.named_scope(spans.MOE_DISPATCH):
        rows = jnp.take(x, tok, axis=0)                      # (M, D)
    cfg_in = ctx.policy.config_for(tags[0])
    cfg_out = ctx.policy.config_for(tags[2])
    k_in = cfg_in.budget_rows(max(1, t * kk // cfg.n_routed))
    k_out = cfg_out.budget_rows(max(1, t * kk // cfg.n_routed))
    sampled = (ctx.key is not None and not cfg_in.is_exact
               and not cfg_out.is_exact and max(k_in, k_out) < t)
    if not sampled:
        with jax.named_scope(spans.MOE_EXPERTS):
            up = _grouped(rows, ws[0], sizes)
            gate = _grouped(rows, ws[1], sizes)
            return _grouped(jax.nn.silu(gate) * up, ws[2], sizes)

    glob = g0 + jnp.arange(e)
    with jax.named_scope(spans.WTACRS_PLAN):
        match = top_e[None] == glob[:, None, None]           # (E, T, K)
        routed = jnp.any(match, -1)
        row_of = jnp.sum(jnp.where(match, pos[None], 0), -1)  # (E, T)

        def keys(tag):
            base = ctx._key_for(tag)
            return jax.vmap(lambda g: jax.random.fold_in(base, g))(glob)

        sel, scale = _expert_plans(cfg_in, _row_norms(x), routed,
                                   row_of, keys("+".join(tags[:2])), k_in)
    up, gate = _expert_linears(rows, (ws[0], ws[1]), sizes, sel, scale)
    z = jax.nn.silu(gate) * up
    with jax.named_scope(spans.WTACRS_PLAN):
        zn = jnp.take(_row_norms(z), row_of)                 # (E, T)
        sel, scale = _expert_plans(cfg_out, zn, routed, row_of,
                                   keys(tags[2]), k_out)
    (y,) = _expert_linears(z, (ws[2],), sizes, sel, scale)
    return y


def apply_moe_share(cfg, p, ctx: cm.Ctx, h) -> Tuple[jax.Array, Dict]:
    """h (B, S, D) -> (B, S, D): this chip's share of a sigmoid-routed
    expert layer plus the shared experts.

    The router runs over all ``cfg.n_routed`` experts; the experts held
    here, ``[expert_offset, expert_offset + n_experts)``, compute their
    part for every token routed to them, with no capacity and no drops,
    as grouped GEMMs (``jax.lax.ragged_dot``) over the routed rows only;
    what absent experts would add is left out, as on one chip of an
    expert-parallel deployment without its exchange.  The shared experts
    run for every token."""
    b, s, d = h.shape
    t = b * s
    x = h.reshape(t, d)
    with jax.named_scope(spans.MOE_ROUTE):
        top_e, w = route_sigmoid(cfg, p, x)
    with jax.named_scope(spans.MOE_DISPATCH):
        tok, sizes, pos = dispatch_share(cfg, top_e)
    y = _expert_ffn_share(cfg, p, ctx, x, top_e, tok, sizes, pos)
    with jax.named_scope(spans.MOE_DISPATCH):
        y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])
        out = jnp.einsum("tk,tkd->td", w, jnp.take(y, pos, axis=0),
                         preferred_element_type=jnp.float32)
    out = out.astype(h.dtype).reshape(b, s, d)
    if "shared" in p:
        out = out + apply_mlp(cfg, p["shared"], ctx, h)
    return out, {}
