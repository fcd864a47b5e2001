"""Plain reference of a dense pre-norm transformer as MiniCPM-2B's
config.json describes it (arXiv:2404.06395): RMS norm, multi-head
attention with rotary positions (split-half rotation, theta from the
config), causal softmax, SwiGLU feed-forward, residual branches scaled by
``residual_scale`` (1.4/sqrt(40)), tied input and output embeddings.
The published ``scale_emb`` and ``dim_model_base`` logit scaling are left
out, as the program leaves them out (a departure, noted in the
configuration file).

Float32 throughout, attention as one softmax over the whole causal
row; each layer is rematerialized so the backward fits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ref_common import Linears, block_key, rms_norm


def init(cfg, key):
    """Weights in the program's parameter layout, f32, from one key."""
    d, f, r = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    hd = cfg["n_heads"] * (cfg["d_head"] or d // cfg["n_heads"])
    kvd = cfg["n_kv_heads"] * (cfg["d_head"] or d // cfg["n_heads"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    unit = {
        "attn": {"wq": normal((r, d, hd)), "wk": normal((r, d, kvd)),
                 "wv": normal((r, d, kvd)), "wo": normal((r, hd, d))},
        "mlp": {"wi": normal((r, d, f)), "wg": normal((r, d, f)),
                "wo": normal((r, f, d))},
        "norm1": {"gamma": jnp.ones((r, d))},
        "norm2": {"gamma": jnp.ones((r, d))},
    }
    params = {"embed": normal((cfg["vocab_size"], d), 0.02),
              "unit": (unit,),
              "final_norm": {"gamma": jnp.ones((d,))}}
    if not cfg["tie_embeddings"]:
        params["head"] = normal((d, cfg["vocab_size"]))
    return params


def _rope(x, theta):
    """x (b, S, H, Dh): rotate the halves (x1, x2) by position * freq."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, p, h, lin, prec):
    b, s, d = h.shape
    nh, nkv = cfg["n_heads"], cfg["n_kv_heads"]
    dh = cfg["d_head"] or d // nh
    eps, rs = cfg["norm_eps"], cfg["residual_scale"]
    x = rms_norm(h, p["norm1"]["gamma"], eps)
    a = p["attn"]
    q, k, v = lin(("attn_q", "attn_k", "attn_v"), x,
                  [a["wq"], a["wk"], a["wv"]])
    q = _rope(q.reshape(b, s, nh, dh), cfg["rope_theta"])
    k = _rope(k.reshape(b, s, nkv, dh), cfg["rope_theta"])
    v = v.reshape(b, s, nkv, dh)
    g = nh // nkv
    q = q.reshape(b, s, nkv, g, dh)
    sc = prec.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = prec.einsum("bhgqk,bkhd->bqhgd", pr, v).reshape(b, s, nh * dh)
    h = h + rs * lin.one("attn_o", o, a["wo"])
    x = rms_norm(h, p["norm2"]["gamma"], eps)
    up, gate = lin(("mlp_wi", "mlp_wg"), x, [p["mlp"]["wi"], p["mlp"]["wg"]])
    return h + rs * lin.one("mlp_wo", jax.nn.silu(gate) * up, p["mlp"]["wo"])


def logits(cfg, params, tokens, prec, budget=None, step_key=None,
           rows=None):
    """(b, S) tokens -> (b, S, V) f32 logits.  With ``step_key`` the
    linears' dW are WTA-CRS estimates at ``budget``."""
    h = jnp.take(params["embed"], tokens, axis=0)
    rows = rows if rows is not None else (0, tokens.shape[0])

    @jax.checkpoint
    def unit(h, xs):
        up, ridx = xs
        lin = Linears(prec, budget, block_key(step_key, ridx, 0), rows, "b0/")
        return _layer(cfg, up[0], h, lin, prec), None

    h, _ = jax.lax.scan(unit, h, (params["unit"],
                                  jnp.arange(cfg["n_layers"])))
    h = rms_norm(h, params["final_norm"]["gamma"], cfg["norm_eps"])
    head = (params["embed"].T if cfg["tie_embeddings"] else params["head"])
    return prec.einsum("bsd,dv->bsv", h, head)


def linears(cfg):
    """[(tag, d_in, d_out, layers, sampled)] of one forward."""
    d, f, r = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    dh = cfg["d_head"] or d // cfg["n_heads"]
    hd, kvd = cfg["n_heads"] * dh, cfg["n_kv_heads"] * dh
    return [("attn_q", d, hd, r, True), ("attn_k", d, kvd, r, True),
            ("attn_v", d, kvd, r, True), ("attn_o", hd, d, r, True),
            ("mlp_wi", d, f, r, True), ("mlp_wg", d, f, r, True),
            ("mlp_wo", f, d, r, True),
            ("head", d, cfg["vocab_size"], 1, False)]


def mixer_flops(cfg, seq_len):
    """FLOPs per sequence of causal attention, forward (QK^T and PV over
    the S(S+1)/2 causal pairs) and backward (twice the forward)."""
    dh = cfg["d_head"] or cfg["d_model"] // cfg["n_heads"]
    hd = cfg["n_heads"] * dh
    return 3 * 2 * hd * seq_len * (seq_len + 1) * cfg["n_layers"]
