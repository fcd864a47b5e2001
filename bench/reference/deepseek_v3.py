"""Plain reference of DeepSeek-V3's decoder (arXiv:2412.19437; latent
attention from arXiv:2405.04434) as Kanana-2-30B-A3B's config.json
describes it, for one chip's share of its experts.

* RMS norm (eps from the config) before attention and before the
  feed-forward; residuals unscaled; untied embedding and head.
* Latent attention without a q low-rank: q = x W_q, split per head into
  q_nope and q_pe; [c, k_pe] = x W_kva; c = RMSNorm(c); [k_nope, v] =
  c W_kvb per head; q_pe and the one-head k_pe rotated pairwise
  ((x0, x1), (x2, x3), ...: ``rope_interleave``); causal softmax of
  q.k / sqrt(nope + rope) over k = [k_nope, k_pe]; o = sum p v; W_o.
* The first ``first_dense_layers`` layers: a SwiGLU of ``d_ff``.  The
  rest: a sigmoid router over all ``router_experts`` (f32), the top-K by
  score plus the frozen bias ``router_bias``, weights the scores over
  their sum times ``routed_scaling``; the experts held here, [offset,
  offset + n_experts), each run densely over every token and are
  weighted by the token's weight for it (zero where not chosen); the
  shared experts, one SwiGLU of ``n_shared_experts * moe_d_ff``, for
  every token.  What absent experts would add is left out, as in the
  program.

WTA-CRS follows the program's conventions: the dense linears through
``ref_common.Linears``; each held expert's weight gradients from its own
plans over the batch's token rows (probability proportional to the row
norm where the token is routed to it, zero elsewhere), keyed by the
expert's global id as ``models/mlp._expert_ffn_share`` documents.
The expert plans span the whole batch, so the reference takes the whole
batch in one block.  Float32 at the highest precision throughout; each
layer is rematerialized.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ref_common import Linears, block_key, rms_norm, tag_seed, wta_plan


def _n_moe(cfg):
    return cfg["n_layers"] - cfg["first_dense_layers"]


def _ef(cfg):
    return cfg["moe_d_ff"] or cfg["d_ff"]


def init(cfg, key):
    """Weights in the program's parameter layout, f32, from one key."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    f, ef, e = cfg["d_ff"], _ef(cfg), cfg["n_experts"]
    nr = cfg["router_experts"] or e
    sf = cfg["n_shared_experts"] * ef
    ks = iter(jax.random.split(key, 32))

    def normal(shape, scale=None):
        scale = 1.0 / math.sqrt(shape[-2]) if scale is None else scale
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    def block(n, mlp):
        return {"norm1": {"gamma": jnp.ones((n, d))},
                "attn": {"wq": normal((n, d, h * (dn + dr))),
                         "wkva": normal((n, d, r + dr)),
                         "kv_norm": {"gamma": jnp.ones((n, r))},
                         "wkvb": normal((n, r, h * (dn + dv))),
                         "wo": normal((n, h * dv, d))},
                "norm2": {"gamma": jnp.ones((n, d))}, **mlp}

    def swiglu(n, width):
        return {"wi": normal((n, d, width)), "wg": normal((n, d, width)),
                "wo": normal((n, width, d))}

    n = _n_moe(cfg)
    moe = {"router": normal((n, d, nr), 0.02),
           "router_bias": normal((n, nr), 1e-2),
           "wi": normal((n, e, d, ef), d ** -0.5),
           "wg": normal((n, e, d, ef), d ** -0.5),
           "wo": normal((n, e, ef, d), ef ** -0.5)}
    if sf:
        moe["shared"] = swiglu(n, sf)
    params = {"embed": normal((cfg["vocab_size"], d), 0.02),
              "unit": (block(n, {"moe": moe}),),
              "final_norm": {"gamma": jnp.ones((d,))},
              "head": normal((d, cfg["vocab_size"]))}
    if cfg["first_dense_layers"]:
        params["dense"] = block(cfg["first_dense_layers"],
                                {"mlp": swiglu(cfg["first_dense_layers"],
                                               f)})
    return params


def _rope_pairs(x, theta):
    """x (b, S, H, D): rotate (x[2i], x[2i+1]) by position * theta^(-2i/D)."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _attention(cfg, p, h, lin, prec):
    b, s, _ = h.shape
    nh, r = cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    x = rms_norm(h, p["norm1"]["gamma"], cfg["norm_eps"])
    a = p["attn"]
    q, kva = lin(("attn_q", "attn_kva"), x, [a["wq"], a["wkva"]])
    q = q.reshape(b, s, nh, dn + dr)
    c = rms_norm(kva[..., :r], a["kv_norm"]["gamma"], cfg["norm_eps"])
    kv = lin.one("attn_kvb", c, a["wkvb"]).reshape(b, s, nh, dn + dv)
    if cfg["rope_interleave"]:
        rope = functools.partial(_rope_pairs, theta=cfg["rope_theta"])
    else:
        raise NotImplementedError("split-half rope: see llama.py")
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
    k_pe = rope(kva[..., r:].reshape(b, s, 1, dr))
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, s, nh, dr))], -1)
    sc = prec.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = prec.einsum("bhqk,bkhd->bqhd", pr, kv[..., dn:])
    return h + lin.one("attn_o", o.reshape(b, s, nh * dv), a["wo"])


def _swiglu(p, x, lin):
    up, gate = lin(("mlp_wi", "mlp_wg"), x, [p["wi"], p["wg"]])
    return lin.one("mlp_wo", jax.nn.silu(gate) * up, p["wo"])


# -- the held experts -------------------------------------------------------

_EXPERT_EQ = {2: ("td,edf->etf", "etf,efd->td", "ekd,ekf->edf"),
              3: ("etd,edf->etf", "etf,efd->etd", "ekd,ekf->edf")}


def _experts_exact(x, ws, prec):
    """Each held expert e over every token: x (T, d) or (E, T, d) ->
    (E, T, f) per w (E, d, f)."""
    return tuple(prec.einsum(_EXPERT_EQ[x.ndim][0], x, w) for w in ws)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _experts(x, ws, idx, scale, prec):
    """``_experts_exact`` whose dW[e] is estimated from the rows idx[e]
    (E, k) with ``scale``."""
    return _experts_exact(x, ws, prec)


def _experts_fwd(x, ws, idx, scale, prec):
    return _experts_exact(x, ws, prec), (x, ws, idx, scale)


def _experts_bwd(prec, res, dys):
    x, ws, idx, scale = res
    eqs = _EXPERT_EQ[x.ndim]
    dx = sum(prec.einsum(eqs[1], dy, jnp.swapaxes(w, 1, 2))
             for dy, w in zip(dys, ws))
    xe = x if x.ndim == 3 else jnp.broadcast_to(x, (ws[0].shape[0],)
                                                + x.shape)
    rows = jax.vmap(lambda a, i: jnp.take(a, i, axis=0))
    h_sub = rows(xe, idx)
    dws = tuple(prec.einsum(eqs[2], h_sub, rows(dy, idx) * scale[..., None])
                for dy in dys)
    return dx, dws, None, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def _expert_plans(norms, routed, keys, k):
    """Per expert: WTA-CRS (ref_common.wta_plan) over the T token rows,
    p proportional to ``norms`` where routed, zero elsewhere; a slot on
    a zero-probability row carries scale 0."""
    p = norms * routed
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    idx, scale = jax.vmap(lambda pr, kk: wta_plan(pr, k, kk))(p, keys)
    live = jnp.take_along_axis(p, idx, axis=1) > 0
    return idx, jnp.where(live, scale, 0.0)


def _norms(x):
    x = jax.lax.stop_gradient(x)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=-1))


def _moe(cfg, p, x, lin, prec):
    """x (b, S, d) -> the held experts' weighted part plus the shared
    experts'."""
    b, s, d = x.shape
    t, kk, e = b * s, cfg["moe_top_k"], cfg["n_experts"]
    nr = cfg["router_experts"] or e
    xf = x.reshape(t, d)
    sc = jax.nn.sigmoid(prec.einsum("td,de->te", xf, p["router"]))
    _, top = jax.lax.top_k(sc + jax.lax.stop_gradient(p["router_bias"]), kk)
    ts = jnp.take_along_axis(sc, top, axis=-1)
    w = ts / (jnp.sum(ts, -1, keepdims=True) + 1e-20) * cfg["routed_scaling"]
    glob = cfg["expert_offset"] + jnp.arange(e)
    match = top[None] == glob[:, None, None]                 # (E, T, K)
    routed = jnp.any(match, -1).astype(jnp.float32)
    weight = jnp.sum(jnp.where(match, w[None], 0.0), -1)     # (E, T)

    ws_in, w_out = (p["wi"], p["wg"]), (p["wo"],)
    k = lin.k_rows(t * kk // nr) if lin.budget is not None else t
    if lin.key is None or lin.budget is None or k >= t:
        up, gate = _experts_exact(xf, ws_in, prec)
        (y,) = _experts_exact(jax.nn.silu(gate) * up, w_out, prec)
    else:
        if b != lin.rows[1]:
            raise ValueError("expert plans span the whole batch: the "
                             "cell's reference_rows must be its batch")

        def keys(tag):
            lk = jax.random.fold_in(jax.random.wrap_key_data(lin.key),
                                    tag_seed(tag))
            return jax.vmap(lambda g: jax.random.fold_in(lk, g))(glob)

        pre = lin.prefix
        idx, scale = _expert_plans(_norms(xf), routed,
                                   keys(pre + "moe_wi+" + pre + "moe_wg"), k)
        up, gate = _experts(xf, ws_in, idx, scale, prec)
        z = jax.nn.silu(gate) * up
        idx, scale = _expert_plans(_norms(z), routed, keys(pre + "moe_wo"),
                                   k)
        (y,) = _experts(z, w_out, idx, scale, prec)
    out = jnp.einsum("et,etd->td", weight, y,
                     precision=jax.lax.Precision.HIGHEST).reshape(b, s, d)
    if "shared" in p:
        out = out + _swiglu(p["shared"], x, lin)
    return out


def _layer(cfg, p, h, lin, prec):
    h = _attention(cfg, p, h, lin, prec)
    x = rms_norm(h, p["norm2"]["gamma"], cfg["norm_eps"])
    if "moe" in p:
        return h + _moe(cfg, p["moe"], x, lin, prec)
    return h + _swiglu(p["mlp"], x, lin)


def logits(cfg, params, tokens, prec, budget=None, step_key=None,
           rows=None):
    """(b, S) tokens -> (b, S, V) f32 logits.  With ``step_key`` the
    linears' dW are WTA-CRS estimates at ``budget``.  Key convention:
    MoE layer (repeat) i takes block_key(step_key, i, 0) under tags
    ``b0/``; leading dense layer i takes fold_in(step_key, n_repeats + i)
    under ``d{i}/``."""
    h = jnp.take(params["embed"], tokens, axis=0)
    rows = rows if rows is not None else (0, tokens.shape[0])
    n = _n_moe(cfg)

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def dense(h, p, i):
        key = None
        if step_key is not None:
            key = jax.random.key_data(jax.random.fold_in(
                jax.random.wrap_key_data(step_key), n + i))
        return _layer(cfg, p, h, Linears(prec, budget, key, rows, f"d{i}/"),
                      prec)

    for i in range(cfg["first_dense_layers"]):
        h = dense(h, jax.tree.map(lambda a, i=i: a[i], params["dense"]), i)

    @jax.checkpoint
    def unit(h, xs):
        up, ridx = xs
        lin = Linears(prec, budget, block_key(step_key, ridx, 0), rows, "b0/")
        return _layer(cfg, up[0], h, lin, prec), None

    h, _ = jax.lax.scan(unit, h, (params["unit"], jnp.arange(n)))
    h = rms_norm(h, params["final_norm"]["gamma"], cfg["norm_eps"])
    return prec.einsum("bsd,dv->bsv", h, params["head"])


def linears(cfg):
    """[(tag, d_in, d_out, layers, sampled)] of one forward.  An expert
    matrix counts as n_moe * K * n_experts / router_experts layers: the
    rows it sees per token in expectation."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    f, ef, e = cfg["d_ff"], _ef(cfg), cfg["n_experts"]
    nr = cfg["router_experts"] or e
    nl, nd, n = cfg["n_layers"], cfg["first_dense_layers"], _n_moe(cfg)
    sf = cfg["n_shared_experts"] * ef
    xl = n * cfg["moe_top_k"] * e / nr
    out = [("attn_q", d, h * (dn + dr), nl, True),
           ("attn_kva", d, r + dr, nl, True),
           ("attn_kvb", r, h * (dn + dv), nl, True),
           ("attn_o", h * dv, d, nl, True),
           ("mlp_wi", d, f, nd, True), ("mlp_wg", d, f, nd, True),
           ("mlp_wo", f, d, nd, True),
           ("moe_router", d, nr, n, False),
           ("moe_wi", d, ef, xl, True), ("moe_wg", d, ef, xl, True),
           ("moe_wo", ef, d, xl, True),
           ("head", d, cfg["vocab_size"], 1, False)]
    if sf:
        out += [("shared_wi", d, sf, n, True), ("shared_wg", d, sf, n, True),
                ("shared_wo", sf, d, n, True)]
    return out


def mixer_flops(cfg, seq_len):
    """FLOPs per sequence of causal latent attention, forward (QK^T over
    nope + rope, PV over v_head_dim, on the S(S+1)/2 causal pairs) and
    backward (twice the forward)."""
    h = cfg["n_heads"]
    width = cfg["qk_nope_dim"] + cfg["qk_rope_dim"] + cfg["v_head_dim"]
    return 3 * h * width * seq_len * (seq_len + 1) * cfg["n_layers"]


def moe_experts_work(cell, operand_bytes=2, out_bytes=4):
    """(FLOPs, bytes) per step of the held experts' GEMMs (the program's
    ``moe_experts`` scope) at the expected routed rows R = T * K *
    n_experts / router_experts a layer: up, gate and down forward and
    their input gradients over R rows; the weight gradients over the
    k rows each expert's plan keeps (R where exact).  Bytes: the bf16
    weights read forward and backward, the f32 weight gradients
    written, the routed rows' operands and results."""
    cfg, mix = cell["config_file"]["config"], cell["mix"]
    t = cell["batch"] * mix["seq_len"]
    d, ef, e = cfg["d_model"], _ef(cfg), cfg["n_experts"]
    nr = cfg["router_experts"] or e
    rows = t * cfg["moe_top_k"] * e / nr
    per_expert = t * cfg["moe_top_k"] // nr
    if mix["estimator"] == "exact":
        kept = rows
    else:
        k = min(max(8, int(round(mix["budget"] * per_expert))), t)
        kept = e * k
    mats = 3 * d * ef
    fl = 2 * mats * (2 * rows + kept)
    by = (2 * e * mats * operand_bytes + e * mats * out_bytes
          + rows * (4 * d + 6 * ef) * operand_bytes
          + kept * 2 * (d + ef) * operand_bytes)
    n = _n_moe(cfg)
    return n * fl, n * by
