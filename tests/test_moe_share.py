"""The expert share (``mlp.apply_moe_share``): sigmoid routing with a
frozen selection bias, no-drop dispatch to the experts held here,
shares that sum to the uncut layer, and per-expert WTA-CRS weight
gradients that are unbiased over each expert's own rows."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import EstimatorKind, WTACRSConfig
from repro.launch import train_steps
from repro.models import common as cm
from repro.models import mlp, registry
from repro.serve.spec import ServeSpec
from repro.train import optim

KEY = jax.random.PRNGKey(0)
CTX = cm.Ctx(policy=cm.Policy(), compute_dtype=jnp.float32)


def _cfg(**kw):
    base = get_config("kanana-2-30b-a3b", reduced=True)
    return dataclasses.replace(base, compute_dtype="float32", **kw)


def _params(cfg, key=KEY):
    return cm.unbox(mlp.init_moe_share(cfg, key, jnp.float32))[0]


def _x(cfg, b=2, s=16, seed=1):
    return jax.random.normal(jax.random.fold_in(KEY, seed),
                             (b, s, cfg.d_model))


def _by_hand(cfg, p, x):
    """Routing in float64 numpy: (chosen experts per token as sets,
    their weights by expert)."""
    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    s = 1.0 / (1.0 + np.exp(-xf @ np.asarray(p["router"], np.float64)))
    sel = np.argsort(-(s + np.asarray(p["router_bias"], np.float64)),
                     axis=-1)[:, :cfg.moe_top_k]
    ts = np.take_along_axis(s, sel, -1)
    w = ts / ts.sum(-1, keepdims=True) * cfg.routed_scaling
    return sel, w


def _dense_share(cfg, p, x):
    """Every held expert over every token, weighted by the token's
    routing weight for it (zero where not chosen), plus shared."""
    xf = x.reshape(-1, cfg.d_model)
    top_e, w = mlp.route_sigmoid(cfg, p, xf)
    out = jnp.zeros_like(xf)
    for e in range(cfg.n_experts):
        g = cfg.expert_offset + e
        we = jnp.sum(jnp.where(top_e == g, w, 0.0), -1)
        z = jax.nn.silu(xf @ p["wg"][e]) * (xf @ p["wi"][e])
        out = out + we[:, None] * (z @ p["wo"][e])
    out = out.reshape(x.shape)
    if "shared" in p:
        out = out + mlp.apply_mlp(cfg, p["shared"], CTX, x)
    return out


def test_router_selection_and_weights_match_hand_computation():
    cfg = _cfg()
    p = _params(cfg)
    x = _x(cfg)
    top_e, w = mlp.route_sigmoid(cfg, p, x.reshape(-1, cfg.d_model))
    sel, want = _by_hand(cfg, p, x)
    np.testing.assert_array_equal(np.sort(np.asarray(top_e), -1),
                                  np.sort(sel, -1))
    order = np.argsort(np.asarray(top_e), -1)
    got = np.take_along_axis(np.asarray(w), order, -1)
    want = np.take_along_axis(want, np.argsort(sel, -1), -1)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_bias_changes_the_choice_but_not_the_weights():
    cfg = _cfg()
    p = _params(cfg)
    x = _x(cfg, b=1, s=2)
    xf = x.reshape(-1, cfg.d_model)
    top0, _ = mlp.route_sigmoid(cfg, p, xf)
    # an expert no token chose gets a bias no score can beat
    unused = int(np.setdiff1d(np.arange(cfg.n_routed),
                              np.asarray(top0))[0])
    pb = dict(p, router_bias=p["router_bias"].at[unused].set(10.0))
    top_e, w = mlp.route_sigmoid(cfg, pb, xf)
    assert np.all(np.any(np.asarray(top_e) == unused, -1))
    s = jax.nn.sigmoid(xf @ p["router"])
    ts = jnp.take_along_axis(s, top_e, -1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(ts / ts.sum(-1, keepdims=True)
                                  * cfg.routed_scaling), rtol=1e-5)
    # the weights are the scores', not the biased scores'
    assert not np.allclose(np.asarray(w)[:, 0], np.asarray(
        (ts[:, 0] + 10.0) / (ts.sum(-1) + 10.0) * cfg.routed_scaling))


def test_forced_imbalance_drops_no_pair():
    """Every token routed to the same held experts (a bias only they can
    reach): all T * K pairs land on them, and each is computed."""
    cfg = _cfg()
    p = _params(cfg)
    bias = jnp.full((cfg.n_routed,), -10.0).at[:cfg.moe_top_k].set(10.0)
    p = dict(p, router_bias=bias)
    x = _x(cfg, b=2, s=32)
    top_e, _ = mlp.route_sigmoid(cfg, p, x.reshape(-1, cfg.d_model))
    _, sizes, pos = mlp.dispatch_share(cfg, top_e)
    t = x.shape[0] * x.shape[1]
    assert np.asarray(sizes)[:cfg.moe_top_k].tolist() == [t] * cfg.moe_top_k
    assert int(np.max(np.asarray(pos))) < t * cfg.moe_top_k   # all held
    got, _ = mlp.apply_moe_share(cfg, p, CTX, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_share(cfg, p, x)),
                               rtol=1e-4, atol=1e-4)


def test_shares_sum_to_the_uncut_layer():
    """The held parts of every share, with the shared experts counted
    once, add up to the layer holding all experts."""
    cfg = _cfg()
    full_cfg = dataclasses.replace(cfg, n_experts=cfg.n_routed)
    full = _params(full_cfg)
    x = _x(cfg)
    want, _ = mlp.apply_moe_share(full_cfg, full, CTX, x)
    n = cfg.n_experts
    total = 0.0
    for off in range(0, cfg.n_routed, n):
        share_cfg = dataclasses.replace(cfg, expert_offset=off)
        share = dict(full, **{k: full[k][off:off + n]
                              for k in ("wi", "wg", "wo")})
        got, _ = mlp.apply_moe_share(share_cfg, share, CTX, x)
        total = total + got
    shared = mlp.apply_mlp(cfg, full["shared"], CTX, x)
    total = total - (cfg.n_routed // n - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # and the share alone is the dense reference of its experts
    got, _ = mlp.apply_moe_share(cfg, dict(full, **{
        k: full[k][:n] for k in ("wi", "wg", "wo")}), CTX, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_dense_share(cfg, dict(full, **{
            k: full[k][:n] for k in ("wi", "wg", "wo")}), x)),
        rtol=1e-4, atol=1e-4)


def test_unrouted_rows_have_zero_weight():
    """Expert plans: every slot with a nonzero scale is on a row routed
    to its expert; an expert with no rows keeps none, and one with at
    most k rows keeps each exactly once."""
    wcfg = WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=0.3, min_rows=2)
    t, k = 40, 6
    rng = np.random.default_rng(0)
    routed = np.zeros((4, t), bool)
    routed[0, rng.choice(t, 25, replace=False)] = True
    routed[1, :] = False                                     # empty
    routed[2, rng.choice(t, 4, replace=False)] = True        # <= k rows
    routed[3, rng.choice(t, 12, replace=False)] = True
    norms = jnp.asarray(rng.random(t) + 0.1, jnp.float32)
    keys = jax.random.split(KEY, 4)
    row_of = jnp.broadcast_to(jnp.arange(t), (4, t))
    sel, scale = mlp._expert_plans(wcfg, norms, jnp.asarray(routed),
                                   row_of, keys, k)
    sel, scale = np.asarray(sel), np.asarray(scale)
    assert np.all(routed[np.arange(4)[:, None], sel] | (scale == 0))
    assert np.all(scale[1] == 0)
    weight = np.zeros(t)
    np.add.at(weight, sel[2], scale[2])
    np.testing.assert_allclose(weight, routed[2].astype(float), atol=1e-6)


def test_expert_dw_estimate_is_unbiased():
    """Over many keys the mean WTA-CRS dW of each held expert tends to
    the exact dW; one key alone is far from it."""
    cfg = _cfg()
    p = _params(cfg)
    x = _x(cfg, b=2, s=32, seed=3)
    r = jax.random.normal(jax.random.fold_in(KEY, 7), x.shape)
    pol = cm.Policy(wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS,
                                        budget=0.3, min_rows=2))

    def loss(w, key, policy):
        ctx = cm.Ctx(policy=policy, key=key, compute_dtype=jnp.float32)
        y, _ = mlp.apply_moe_share(cfg, dict(p, wi=w[0], wo=w[1]), ctx, x)
        return jnp.sum(y * r)

    ws = (p["wi"], p["wo"])
    exact = jax.grad(loss)(ws, KEY, cm.Policy())
    keys = jax.random.split(jax.random.PRNGKey(11), 512)
    est = jax.jit(jax.vmap(lambda kk: jax.grad(loss)(ws, kk, pol)))(keys)
    for name, g_exact, g_est in zip(("wi", "wo"), exact, est):
        ge = np.asarray(g_exact)
        mean = np.asarray(jnp.mean(g_est, 0))
        one = np.asarray(g_est[0])
        err = np.linalg.norm(mean - ge) / np.linalg.norm(ge)
        err1 = np.linalg.norm(one - ge) / np.linalg.norm(ge)
        assert err < 0.1, (name, err)
        assert err1 > 2 * err, (name, err1, err)


def test_router_bias_is_frozen_by_the_step():
    cfg = get_config("kanana-2-30b-a3b", reduced=True)
    state = train_steps.init_train_state(cfg, KEY)
    pol = cm.Policy(wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS,
                                        budget=0.3, min_rows=2))
    step = train_steps.make_train_step(
        cfg, pol, optim.AdamWConfig(), optim.linear_warmup_constant(1e-3))
    batch = registry.make_synthetic_batch(cfg, 2, 32, KEY)
    new, _ = jax.jit(step)(state, batch)
    old_moe = state["params"]["unit"][0]["moe"]
    new_moe = new["params"]["unit"][0]["moe"]
    np.testing.assert_array_equal(np.asarray(new_moe["router_bias"]),
                                  np.asarray(old_moe["router_bias"]))
    assert not np.array_equal(np.asarray(new_moe["router"]),
                              np.asarray(old_moe["router"]))
    assert not np.array_equal(np.asarray(new_moe["wi"]),
                              np.asarray(old_moe["wi"]))


def test_parameter_counts_of_the_share():
    cfg = get_config("kanana-2-30b-a3b")
    share = dataclasses.replace(cfg, n_layers=5, n_experts=8,
                                vocab_size=16032)
    assert cfg.n_params() == 30_670_616_448
    assert share.n_params() == 424_938_496
    # router over 128 experts, 6 of them and the shared experts active
    assert 3.5e9 < cfg.n_active_params() < 3.7e9
    assert share.n_active_params() < share.n_params()


@pytest.mark.parametrize("arch", ["kanana-2-30b-a3b"])
def test_serving_refuses_latent_attention(arch):
    with pytest.raises(ValueError, match="latent attention"):
        ServeSpec(arch=arch)


_RAGGED_DOT = jax.lax.ragged_dot


def _nan_past_groups(a, sizes):
    valid = jnp.arange(a.shape[0]) < jnp.sum(sizes)
    return jnp.where(valid[:, None], a, jnp.nan)


@jax.custom_vjp
def _ragged_dot_undefined_tail(lhs, rhs, group_sizes):
    """``jax.lax.ragged_dot`` whose rows past the groups read NaN, in the
    result and in the input gradient: the contract leaves them undefined,
    and only CPU happens to write zeros there."""
    return _nan_past_groups(_RAGGED_DOT(lhs, rhs, group_sizes), group_sizes)


def _undefined_tail_fwd(lhs, rhs, group_sizes):
    return (_ragged_dot_undefined_tail(lhs, rhs, group_sizes),
            (lhs, rhs, group_sizes))


def _undefined_tail_bwd(res, g):
    lhs, rhs, sizes = res
    _, vjp = jax.vjp(lambda a, b: _RAGGED_DOT(a, b, sizes), lhs, rhs)
    d_lhs, d_rhs = vjp(g)
    return _nan_past_groups(d_lhs, sizes), d_rhs, None


_ragged_dot_undefined_tail.defvjp(_undefined_tail_fwd, _undefined_tail_bwd)


@pytest.mark.parametrize("budget", [None, 0.3], ids=["exact", "wta_crs"])
def test_rows_past_the_groups_reach_nothing(budget, monkeypatch):
    """The dispatch buffer's padding rows (past the held experts' rows)
    leave the loss and every gradient as they are, whatever the grouped
    GEMM writes there."""
    cfg = _cfg()
    params = registry.init_params(cfg, KEY)[0]
    batch = registry.make_synthetic_batch(cfg, 2, 32, KEY)
    pol = (cm.Policy() if budget is None else cm.Policy(
        wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=budget,
                            min_rows=2)))
    step_key = jax.random.PRNGKey(9)

    def loss(p):
        return registry.loss_fn(cfg, p, batch, pol, key=step_key)[0]

    want_l, want_g = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(jax.lax, "ragged_dot", _ragged_dot_undefined_tail)
    got_l, got_g = jax.value_and_grad(loss)(params)
    assert float(got_l) == float(want_l)
    flat = jax.tree_util.tree_flatten_with_path(got_g)[0]
    for (path, a), c in zip(flat, jax.tree.leaves(want_g)):
        name = jax.tree_util.keystr(path)
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5,
                                   atol=1e-7, err_msg=name)
