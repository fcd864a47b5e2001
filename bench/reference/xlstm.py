"""Plain reference of the xLSTM language model as the program defines it
(arXiv:2405.04517; repro.models.ssm's block equations): pre-norm
residual blocks alternating mLSTM (matrix memory, exponential gating with
the log-space stabilizer m) and sLSTM (scalar memory, block-diagonal
recurrent head mixing), a final RMS norm and an untied head.

Float32 throughout; the recurrences are token-by-token scans,
rematerialized per chunk of 128 tokens so the backward fits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ref_common import Linears, block_key, rms_norm

CHUNK = 128


def init(cfg, key):
    """Weights in the program's parameter layout, f32, from one key."""
    d, nh = cfg["d_model"], cfg["n_heads"]
    di = cfg["ssm_expand"] * d
    r = cfg["n_layers"] // 2
    dh_s = d // nh
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    mlstm = {
        "up": normal((r, d, 2 * di)), "wq": normal((r, di, di)),
        "wk": normal((r, di, di)), "wv": normal((r, di, di)),
        "w_if": normal((r, di, 2 * nh), 0.02),
        "if_bias": jnp.tile(jnp.concatenate(
            [jnp.zeros((nh,)), 3.0 * jnp.ones((nh,))]), (r, 1)),
        "down": normal((r, di, d)),
    }
    slstm = {
        "w_in": normal((r, d, 4 * d)),
        "r": normal((r, nh, dh_s, 4 * dh_s), 0.02),
        "bias": jnp.tile(jnp.concatenate(
            [jnp.zeros((2 * d,)), 3.0 * jnp.ones((d,)), jnp.zeros((d,))]),
            (r, 1)),
        "down": normal((r, d, d)),
    }
    ones = {"gamma": jnp.ones((r, d))}
    return {
        "embed": normal((cfg["vocab_size"], d), 0.02),
        "unit": ({"mlstm": mlstm, "norm1": ones},
                 {"slstm": slstm, "norm1": dict(ones)}),
        "final_norm": {"gamma": jnp.ones((d,))},
        "head": normal((d, cfg["vocab_size"])),
    }


def _scan_chunks(step, state, xs):
    n = jax.tree.leaves(xs)[0].shape[0]
    c = min(CHUNK, n)
    xs = jax.tree.map(lambda x: x.reshape((n // c, c) + x.shape[1:]), xs)
    state, ys = jax.lax.scan(
        jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)), state, xs)
    return state, jax.tree.map(lambda y: y.reshape((n,) + y.shape[2:]), ys)


def _mlstm(cfg, p, x, lin, prec):
    b, s, _ = x.shape
    nh = cfg["n_heads"]
    di = cfg["ssm_expand"] * cfg["d_model"]
    dh = di // nh
    xs, z = jnp.split(lin.one("mlstm_up", x, p["up"]), 2, axis=-1)
    q = lin.one("mlstm_q", xs, p["wq"]).reshape(b, s, nh, dh)
    k = lin.one("mlstm_k", xs, p["wk"]).reshape(b, s, nh, dh)
    v = lin.one("mlstm_v", xs, p["wv"]).reshape(b, s, nh, dh)
    gif = lin.one("mlstm_if", xs, p["w_if"]) + p["if_bias"]
    i_raw, f_raw = jnp.split(gif, 2, axis=-1)

    def cell(st, t):
        c, n, m = st
        qt, kt, vt, it, ft = t
        logf = jax.nn.log_sigmoid(ft)
        m_new = jnp.maximum(logf + m, it)
        fg = jnp.exp(logf + m - m_new)[..., None]
        ig = jnp.exp(it - m_new)[..., None]
        ks_ = kt / math.sqrt(dh)
        c = fg[..., None] * c + (ig * vt)[..., None, :] * ks_[..., :, None]
        n = fg * n + ig * ks_
        num = prec.einsum("bhd,bhde->bhe", qt, c)
        den = jnp.maximum(jnp.abs(jnp.sum(qt * n, axis=-1)),
                          jnp.exp(-m_new))[..., None]
        return (c, n, m_new), num / den

    st = (jnp.zeros((b, nh, dh, dh)), jnp.zeros((b, nh, dh)),
          jnp.full((b, nh), -1e30))
    seq = lambda a: jnp.moveaxis(a, 1, 0)
    _, hs = _scan_chunks(cell, st, tuple(map(seq, (q, k, v, i_raw, f_raw))))
    y = jnp.moveaxis(hs, 0, 1).reshape(b, s, di) * jax.nn.silu(z)
    return lin.one("mlstm_down", y, p["down"])


def _slstm(cfg, p, x, lin, prec):
    b, s, d = x.shape
    nh = cfg["n_heads"]
    dh = d // nh
    xin = lin.one("slstm_in", x, p["w_in"])
    r, bias = p["r"], p["bias"].reshape(1, nh, 4 * dh)

    def cell(st, xt):
        c, n, m, h = st
        g = (xt.reshape(-1, nh, 4 * dh)
             + prec.einsum("bhd,hde->bhe", h, r) + bias)
        zr, ir, fr, orr = jnp.split(g, 4, axis=-1)
        logf = jax.nn.log_sigmoid(fr)
        m_new = jnp.maximum(logf + m, ir)
        fg = jnp.exp(logf + m - m_new)
        ig = jnp.exp(ir - m_new)
        c = fg * c + ig * jnp.tanh(zr)
        n = fg * n + ig
        h = jax.nn.sigmoid(orr) * c / jnp.maximum(n, 1.0)
        return (c, n, m_new, h), h

    z0 = jnp.zeros((b, nh, dh))
    _, hs = _scan_chunks(cell, (z0, z0, z0 - 1e30, z0),
                         jnp.moveaxis(xin, 1, 0))
    return lin.one("slstm_down", jnp.moveaxis(hs, 0, 1).reshape(b, s, d),
                   p["down"])


def logits(cfg, params, tokens, prec, budget=None, step_key=None,
           rows=None):
    """(b, S) tokens -> (b, S, V) f32 logits.  With ``step_key`` the
    linears' dW are WTA-CRS estimates at ``budget``."""
    h = jnp.take(params["embed"], tokens, axis=0)
    eps = cfg["norm_eps"]
    rs = cfg["residual_scale"]
    rows = rows if rows is not None else (0, tokens.shape[0])

    def unit(h, xs):
        up, ridx = xs
        for j, (name, fn) in enumerate((("mlstm", _mlstm),
                                        ("slstm", _slstm))):
            lin = Linears(prec, budget, block_key(step_key, ridx, j), rows,
                          f"b{j}/")
            x = rms_norm(h, up[j]["norm1"]["gamma"], eps)
            h = h + rs * fn(cfg, up[j][name], x, lin, prec)
        return h, None

    n_rep = cfg["n_layers"] // 2
    h, _ = jax.lax.scan(unit, h, (params["unit"], jnp.arange(n_rep)))
    h = rms_norm(h, params["final_norm"]["gamma"], eps)
    return prec.einsum("bsd,dv->bsv", h, params["head"])


def linears(cfg):
    """[(tag, d_in, d_out, layers, sampled)] of one forward."""
    d, nh = cfg["d_model"], cfg["n_heads"]
    di = cfg["ssm_expand"] * d
    r = cfg["n_layers"] // 2
    return [("mlstm_up", d, 2 * di, r, True), ("mlstm_q", di, di, r, True),
            ("mlstm_k", di, di, r, True), ("mlstm_v", di, di, r, True),
            ("mlstm_if", di, 2 * nh, r, True), ("mlstm_down", di, d, r, True),
            ("slstm_in", d, 4 * d, r, True), ("slstm_down", d, d, r, True),
            ("head", d, cfg["vocab_size"], 1, False)]


def mixer_flops(cfg, seq_len):
    """FLOPs per sequence of the recurrences, forward and backward (the
    backward taken as twice the forward).  mLSTM per token and head:
    C = f*C + (i v) k^T is 3 dh^2, h = C q is 2 dh^2; sLSTM per token
    and head: the recurrent product h R is 2 dh * 4 dh."""
    d, nh = cfg["d_model"], cfg["n_heads"]
    dh_m = cfg["ssm_expand"] * d // nh
    dh_s = d // nh
    r = cfg["n_layers"] // 2
    per_tok = r * nh * (5 * dh_m ** 2 + 8 * dh_s ** 2)
    return 3 * per_tok * seq_len
