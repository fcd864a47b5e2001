"""State-space and recurrent blocks: Mamba2 (SSD), mLSTM, sLSTM.

Mamba2 uses the chunked SSD formulation (quadratic only within a chunk,
linear across chunks via a carried state), which is both the published
algorithm and the TPU-friendly one: intra-chunk work is MXU einsums,
the inter-chunk recurrence is a short scan over L/chunk steps.

mLSTM/sLSTM (xLSTM, arXiv:2405.04517) use exponential gating with the
log-space max-stabilizer m_t.  The mLSTM is linear in its matrix memory,
so a sequence runs chunkwise-parallel like Mamba2: masked matmuls inside
a chunk, one carried state across chunks (xLSTM's own training form,
arXiv:2405.04517 App. B).  The sLSTM is non-linear in h and runs an
outer scan over sequence chunks with the inner chunk rematerialized, so
backward stores only chunk-boundary states.

All in/out projections route through ctx.linear and are therefore
WTA-CRS-compressible; the recurrences themselves are not weight GEMMs
and keep exact gradients (consistent with the paper's scope, Fig. 4).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import spans
from repro.models import common as cm


# ---------------------------------------------------------------------------
# Mamba2
# ---------------------------------------------------------------------------

def mamba_dims(cfg):
    di = cfg.ssm_expand * cfg.d_model
    nh = di // cfg.ssm_head_dim
    return di, nh, cfg.ssm_head_dim, cfg.ssm_state


def init_mamba(cfg, key, dtype):
    d = cfg.d_model
    di, nh, hd, n = mamba_dims(cfg)
    conv_dim = di + 2 * n
    ks = jax.random.split(key, 6)
    return {
        "in_proj": cm.dense_init(ks[0], (d, 2 * di + 2 * n + nh),
                                 ("embed", "ssm_inner"), dtype),
        "conv_w": cm.dense_init(ks[1], (cfg.ssm_conv, conv_dim),
                                (None, "ssm_inner"), dtype, scale=0.5),
        "conv_b": cm.zeros_init((conv_dim,), ("ssm_inner",), dtype),
        "a_log": cm.Boxed(jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                          (None,)),
        "d_skip": cm.ones_init((nh,), (None,), jnp.float32),
        "dt_bias": cm.zeros_init((nh,), (None,), jnp.float32),
        "norm_g": cm.ones_init((di,), ("ssm_inner",), dtype),
        "out_proj": cm.dense_init(ks[2], (di, d), ("ssm_inner", "embed"),
                                  dtype),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x: (B, L, C), w: (K, C).  Returns (y, state)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    new_state = xp[:, -(k - 1):, :] if k > 1 else None
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(k))
    return (y + b).astype(x.dtype), new_state


def _ssd_chunked(xh, dt, a, bmat, cmat, chunk: int):
    """Chunked SSD.  xh: (B,L,H,P), dt: (B,L,H), a: (H,) negative,
    bmat/cmat: (B,L,N).  Returns (y: (B,L,H,P), final_state (B,H,N,P))."""
    b, l, h, p = xh.shape
    n = bmat.shape[-1]
    chunk = min(chunk, l)
    nc = l // chunk
    xc = xh.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    bc = bmat.reshape(b, nc, chunk, n)
    cc = cmat.reshape(b, nc, chunk, n)

    da = dtc * a[None, None, None, :]                    # (B,nc,c,H) <= 0
    seg = jnp.cumsum(da, axis=2)                         # decay from chunk
    total = seg[:, :, -1, :]                             # (B,nc,H)

    # intra-chunk: Y[t] = sum_{s<=t} exp(seg_t - seg_s) (C_t.B_s) dt_s x_s
    scores = jnp.einsum("bqtn,bqsn->bqts", cc, bc)       # (B,nc,c,c)
    decay = seg[:, :, :, None, :] - seg[:, :, None, :, :]  # (B,nc,t,s,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    # double-where: never exp() masked (positive) decays, else backward
    # produces 0 * inf = NaN through the mask
    lmat = jnp.where(causal, jnp.exp(jnp.where(causal, decay, 0.0)), 0.0)
    y_intra = jnp.einsum("bqts,bqtsh,bqsh,bqshp->bqthp",
                         scores, lmat, dtc, xc)

    # chunk summaries: S_q = sum_s exp(total - seg_s) dt_s B_s x_s^T
    w_end = jnp.exp(total[:, :, None, :] - seg)          # (B,nc,c,H)
    s_q = jnp.einsum("bqsn,bqsh,bqsh,bqshp->bqhnp",
                     bc, w_end, dtc, xc)                 # (B,nc,H,N,P)

    # inter-chunk recurrence over q: h_q = exp(total_q) h_{q-1} + S_q
    def step(hprev, xs):
        tot_q, s_qq = xs                                 # (B,H), (B,H,N,P)
        h_new = jnp.exp(tot_q)[..., None, None] * hprev + s_qq
        return h_new, hprev                              # emit state BEFORE q

    h0 = jnp.zeros((b, h, n, p), jnp.float32)
    h_final, h_before = jax.lax.scan(
        step, h0, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(s_q, 1, 0)))
    h_before = jnp.moveaxis(h_before, 0, 1)              # (B,nc,H,N,P)

    y_inter = jnp.einsum("bqtn,bqth,bqhnp->bqthp",
                         cc, jnp.exp(seg), h_before)
    y = (y_intra + y_inter).reshape(b, l, h, p)
    return y, h_final


def apply_mamba(cfg, p, ctx: cm.Ctx, h, chunk: int = 256,
                return_state: bool = False):
    """h: (B, L, D) -> (B, L, D) [, decode state]."""
    bsz, l, d = h.shape
    di, nh, hd, n = mamba_dims(cfg)
    proj = ctx.linear("mamba_in", h, p["in_proj"])
    z, xbc_raw, dt_raw = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    xbc, conv_state = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    x, bmat, cmat = jnp.split(xbc, [di, di + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = x.reshape(bsz, l, nh, hd).astype(jnp.float32)
    y, ssm_state = _ssd_chunked(xh, dt, a, bmat.astype(jnp.float32),
                                cmat.astype(jnp.float32), chunk)
    y = y + p["d_skip"][None, None, :, None] * xh
    y = y.reshape(bsz, l, di).astype(h.dtype)
    y = cm.rms_norm(y, p["norm_g"], cfg.norm_eps) * jax.nn.silu(z)
    out = ctx.linear("mamba_out", y, p["out_proj"])
    if return_state:
        return out, {"conv": conv_state, "ssm": ssm_state}
    return out


def mamba_decode_init(cfg, batch: int, dtype):
    di, nh, hd, n = mamba_dims(cfg)
    conv_dim = di + 2 * n
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, nh, n, hd), jnp.float32),
    }


def mamba_decode_step(cfg, p, ctx: cm.Ctx, h1, state):
    """h1: (B, 1, D) -> (B, 1, D); O(1) state update."""
    bsz = h1.shape[0]
    di, nh, hd, n = mamba_dims(cfg)
    proj = ctx.linear("mamba_in", h1, p["in_proj"])
    z, xbc, dt_raw = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                   state["conv"])
    xbc = jax.nn.silu(xbc)
    x, bmat, cmat = jnp.split(xbc, [di, di + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])[:, 0]   # (B,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = x.reshape(bsz, nh, hd).astype(jnp.float32)
    da = jnp.exp(dt * a[None, :])                               # (B,H)
    upd = jnp.einsum("bh,bn,bhp->bhnp", dt, bmat[:, 0].astype(jnp.float32),
                     xh)
    ssm = da[..., None, None] * state["ssm"] + upd
    y = jnp.einsum("bn,bhnp->bhp", cmat[:, 0].astype(jnp.float32), ssm)
    y = y + p["d_skip"][None, :, None] * xh
    y = y.reshape(bsz, 1, di).astype(h1.dtype)
    y = cm.rms_norm(y, p["norm_g"], cfg.norm_eps) * jax.nn.silu(z)
    out = ctx.linear("mamba_out", y, p["out_proj"])
    return out, {"conv": conv_state, "ssm": ssm}


# ---------------------------------------------------------------------------
# mLSTM (matrix memory, exponential gating)
# ---------------------------------------------------------------------------

def mlstm_dims(cfg):
    di = cfg.ssm_expand * cfg.d_model
    nh = cfg.n_heads
    return di, nh, di // nh


def init_mlstm(cfg, key, dtype):
    d = cfg.d_model
    di, nh, dh = mlstm_dims(cfg)
    ks = jax.random.split(key, 7)
    return {
        "up": cm.dense_init(ks[0], (d, 2 * di), ("embed", "ssm_inner"),
                            dtype),
        "wq": cm.dense_init(ks[1], (di, di), ("ssm_inner", "ssm_inner"),
                            dtype),
        "wk": cm.dense_init(ks[2], (di, di), ("ssm_inner", "ssm_inner"),
                            dtype),
        "wv": cm.dense_init(ks[3], (di, di), ("ssm_inner", "ssm_inner"),
                            dtype),
        "w_if": cm.dense_init(ks[4], (di, 2 * nh), ("ssm_inner", None),
                              dtype, scale=0.02),
        "if_bias": cm.Boxed(
            jnp.concatenate([jnp.zeros((nh,)), 3.0 * jnp.ones((nh,))]
                            ).astype(jnp.float32), (None,)),
        "down": cm.dense_init(ks[5], (di, d), ("ssm_inner", "embed"), dtype),
    }


def _mlstm_cell_step(state, qkvif):
    """One stabilized mLSTM step.  state: (C (B,H,dh,dh), n (B,H,dh),
    m (B,H)).  qkvif: q,k,v (B,H,dh), i_raw,f_raw (B,H)."""
    c, n, m = state
    q, k, v, i_raw, f_raw = qkvif
    dh = q.shape[-1]
    logf = -jax.nn.softplus(-f_raw)                     # log sigmoid(f)
    m_new = jnp.maximum(logf + m, i_raw)
    fg = jnp.exp(logf + m - m_new)[..., None]
    ig = jnp.exp(i_raw - m_new)[..., None]
    k_sc = k / jnp.sqrt(dh)
    c_new = fg[..., None] * c + (ig * v)[..., None, :] * k_sc[..., :, None]
    n_new = fg * n + ig * k_sc
    num = jnp.einsum("bhd,bhde->bhe", q, c_new)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n_new)),
                      jnp.exp(-m_new))[..., None]
    h = num / den
    return (c_new, n_new, m_new), h


def _recurrent_over_chunks(cell_step, state, xs_seq, chunk: int):
    """scan over chunks with rematerialized inner scans.

    xs_seq: pytree with leading (L, ...) time axis.  Returns (state, ys)."""
    l = jax.tree.leaves(xs_seq)[0].shape[0]
    chunk = min(chunk, l)
    nc = l // chunk
    xs_c = jax.tree.map(
        lambda x: x.reshape((nc, chunk) + x.shape[1:]), xs_seq)

    @jax.checkpoint
    def chunk_step(st, xs_chunk):
        return jax.lax.scan(cell_step, st, xs_chunk)

    state, ys = jax.lax.scan(chunk_step, state, xs_c)
    ys = jax.tree.map(lambda y: y.reshape((l,) + y.shape[2:]), ys)
    return state, ys


def _mm(spec, *operands):
    """einsum at f32 accuracy: bf16 operands multiply exactly, f32 ones
    take the MXU's multi-pass route; every sum accumulates in f32."""
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _mlstm_chunkwise(state, q, k, v, i_raw, logf, chunk: int):
    """The stabilized mLSTM over a sequence, chunkwise-parallel.

    q, k, v: (B, L, H, dh); i_raw, logf (log forget gate): (B, L, H) f32;
    state (C, n, m) as in ``_mlstm_cell_step``.  Returns the state after
    the last token and h: (B, L, H, dh) f32, as scanning
    ``_mlstm_cell_step`` over the tokens would, up to rounding.

    With F the cumulative log forget gate from a chunk's start and (C, n,
    m) the state carried into it, unstabilized C_t = e^(F_t + m) C +
    sum_{s<=t} e^(D_ts) k_s v_s^T / sqrt(dh), D_ts = F_t - F_s + i_s, and
    the recurrent stabilizer m_t = max(logf_t + m_{t-1}, i_t) unrolls to
    the row maximum of (F_t + m, D_t.).  So each token's h_t is computed
    with its own m_t, and the chunk's last row is the state at its end.
    The chunk's body is rematerialized: the backward keeps only the
    carried state at each chunk boundary and the chunk's inputs, never a
    per-token (dh, dh) memory, and rebuilds the (c, c) and (c, dh) blocks
    from them (on a v5e that is faster than storing them, and smaller).
    A tail past the last whole chunk is padded with gates that add
    nothing (i = -inf, logf = 0), so it moves neither h nor the final
    state.
    """
    bsz, l, nh, dh = q.shape
    chunk = min(chunk, l)
    nc = -(-l // chunk)
    pad = nc * chunk - l
    if pad:
        tail = lambda x, val: jnp.pad(
            x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2),
            constant_values=val)
        q, k, v = tail(q, 0), tail(k, 0), tail(v, 0)
        i_raw, logf = tail(i_raw, -jnp.inf), tail(logf, 0.0)
    # (B, L, H, ...) -> (nc, B, H, c, ...)
    to_chunks = lambda x: jnp.moveaxis(
        x.reshape((bsz, nc, chunk) + x.shape[2:]), (1, 3), (0, 2))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    after = jnp.tril(causal, -1)                 # (r, s): r > s
    scale = 1.0 / math.sqrt(dh)

    @jax.checkpoint
    def step(carry, xs):
        c, n, m = carry                          # (B,H,dh,dh), (B,H,dh), (B,H)
        qc, kc, vc, ic, fc = xs                  # (B,H,c,dh) x3, (B,H,c) x2
        fcum = jnp.cumsum(fc, axis=-1)
        # F_t - F_s as a sum over s < r <= t of its own, not a difference
        # of two prefixes: no cancellation, in the values or the backward
        seg = jnp.cumsum(jnp.where(after, fc[..., :, None], 0.0), axis=-2)
        log_d = seg + ic[..., None, :]
        m_t = jnp.maximum(fcum + m[..., None],
                          jnp.max(jnp.where(causal, log_d, -jnp.inf), -1))
        # double-where: masked decays (s > t) can be large and positive;
        # they never reach exp, so the backward has no 0 * inf
        decay = jnp.where(
            causal, jnp.exp(jnp.where(causal, log_d - m_t[..., None], 0.0)),
            0.0)                                 # (B,H,t,s) <= 1
        carried = jnp.exp(fcum + m[..., None] - m_t)   # (B,H,t) <= 1
        p = _mm("bhtd,bhsd->bhts", qc, kc) * scale * decay
        num = (_mm("bhts,bhsd->bhtd", p, vc)
               + carried[..., None] * _mm("bhtd,bhde->bhte", qc, c))
        qn = p.sum(-1) + carried * _mm("bhtd,bhd->bht", qc, n)
        h = num / jnp.maximum(jnp.abs(qn), jnp.exp(-m_t))[..., None]

        w_end = decay[..., -1, :] * scale        # (B,H,s)
        f_end = carried[..., -1]                 # (B,H)
        c = f_end[..., None, None] * c + _mm(
            "bhsd,bhse->bhde", kc * w_end[..., None], vc)
        n = f_end[..., None] * n + _mm("bhs,bhsd->bhd", w_end, kc)
        return (c, n, m_t[..., -1]), h

    state, hs = jax.lax.scan(
        step, state, tuple(map(to_chunks, (q, k, v, i_raw, logf))))
    hs = jnp.moveaxis(hs, (0, 2), (1, 3)).reshape(bsz, nc * chunk, nh, dh)
    return state, hs[:, :l]


def apply_mlstm(cfg, p, ctx: cm.Ctx, h, chunk: int = 128,
                return_state: bool = False):
    bsz, l, d = h.shape
    di, nh, dh = mlstm_dims(cfg)
    up = ctx.linear("mlstm_up", h, p["up"])
    xs, z = jnp.split(up, 2, axis=-1)
    q = ctx.linear("mlstm_q", xs, p["wq"]).reshape(bsz, l, nh, dh)
    k = ctx.linear("mlstm_k", xs, p["wk"]).reshape(bsz, l, nh, dh)
    v = ctx.linear("mlstm_v", xs, p["wv"]).reshape(bsz, l, nh, dh)
    gif = (ctx.linear("mlstm_if", xs, p["w_if"]).astype(jnp.float32)
           + p["if_bias"][None, None, :])
    i_raw, f_raw = jnp.split(gif, 2, axis=-1)           # (B,L,H)

    state = mlstm_decode_init(cfg, bsz)
    state = (state["c"], state["n"], state["m"])
    with jax.named_scope(spans.MLSTM_SCAN):
        (cs, ns, ms), hs = _mlstm_chunkwise(
            state, q, k, v, i_raw, -jax.nn.softplus(-f_raw), chunk)
    hs = hs.reshape(bsz, l, di)
    y = hs.astype(h.dtype) * jax.nn.silu(z)
    out = ctx.linear("mlstm_down", y, p["down"])
    if return_state:
        return out, {"c": cs, "n": ns, "m": ms}
    return out


def mlstm_decode_init(cfg, batch: int):
    di, nh, dh = mlstm_dims(cfg)
    return {"c": jnp.zeros((batch, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, nh, dh), jnp.float32),
            "m": jnp.full((batch, nh), -1e30, jnp.float32)}


def mlstm_decode_step(cfg, p, ctx: cm.Ctx, h1, state):
    bsz = h1.shape[0]
    di, nh, dh = mlstm_dims(cfg)
    up = ctx.linear("mlstm_up", h1, p["up"])
    xs, z = jnp.split(up, 2, axis=-1)
    q = ctx.linear("mlstm_q", xs, p["wq"]).reshape(bsz, nh, dh)
    k = ctx.linear("mlstm_k", xs, p["wk"]).reshape(bsz, nh, dh)
    v = ctx.linear("mlstm_v", xs, p["wv"]).reshape(bsz, nh, dh)
    gif = (ctx.linear("mlstm_if", xs, p["w_if"]).astype(jnp.float32)
           + p["if_bias"][None, None, :])[:, 0]
    i_raw, f_raw = jnp.split(gif, 2, axis=-1)
    st = (state["c"], state["n"], state["m"])
    (c, n, m), h_out = _mlstm_cell_step(
        st, (q.astype(jnp.float32), k.astype(jnp.float32),
             v.astype(jnp.float32), i_raw, f_raw))
    y = h_out.reshape(bsz, 1, di).astype(h1.dtype) * jax.nn.silu(z)
    out = ctx.linear("mlstm_down", y, p["down"])
    return out, {"c": c, "n": n, "m": m}


# ---------------------------------------------------------------------------
# sLSTM (scalar memory, recurrent head-mixing)
# ---------------------------------------------------------------------------

def slstm_dims(cfg):
    nh = cfg.n_heads
    return cfg.d_model, nh, cfg.d_model // nh


def init_slstm(cfg, key, dtype):
    d, nh, dh = slstm_dims(cfg)
    ks = jax.random.split(key, 3)
    return {
        "w_in": cm.dense_init(ks[0], (d, 4 * d), ("embed", "ssm_inner"),
                              dtype),
        # recurrent block-diagonal per-head mixing for the 4 gates
        "r": cm.dense_init(ks[1], (nh, dh, 4 * dh), (None, None, None),
                           dtype, scale=0.02),
        "bias": cm.Boxed(
            jnp.concatenate([jnp.zeros((2 * d,)), 3.0 * jnp.ones((d,)),
                             jnp.zeros((d,))]).astype(jnp.float32), (None,)),
        "down": cm.dense_init(ks[2], (d, d), ("ssm_inner", "embed"), dtype),
    }


def _slstm_cell_step_factory(p, nh, dh):
    r = p["r"].astype(jnp.float32)
    bias = p["bias"]

    def step(state, x_t):
        c, n, m, h_prev = state                      # (B,H,dh) x3, (B,H,dh)
        rec = jnp.einsum("bhd,hde->bhe", h_prev, r)  # (B,H,4dh)
        gates = (x_t.reshape((-1, nh, 4 * dh)) + rec
                 + bias.reshape((1, nh, 4 * dh)))
        zr, ir, fr, orr = jnp.split(gates, 4, axis=-1)
        logf = -jax.nn.softplus(-fr)
        m_new = jnp.maximum(logf + m, ir)
        fg = jnp.exp(logf + m - m_new)
        ig = jnp.exp(ir - m_new)
        zt = jnp.tanh(zr)
        c_new = fg * c + ig * zt
        n_new = fg * n + ig
        h_new = jax.nn.sigmoid(orr) * c_new / jnp.maximum(n_new, 1.0)
        return (c_new, n_new, m_new, h_new), h_new

    return step


def apply_slstm(cfg, p, ctx: cm.Ctx, h, chunk: int = 256,
                return_state: bool = False):
    bsz, l, d = h.shape
    _, nh, dh = slstm_dims(cfg)
    x = ctx.linear("slstm_in", h, p["w_in"]).astype(jnp.float32)
    xs = jnp.moveaxis(x, 1, 0)                       # (L,B,4d)
    state = slstm_decode_init(cfg, bsz)
    state = (state["c"], state["n"], state["m"], state["h"])
    step = _slstm_cell_step_factory(p, nh, dh)
    with jax.named_scope(spans.SLSTM_SCAN):
        (c, n, m, hh), hs = _recurrent_over_chunks(step, state, xs, chunk)
    hs = jnp.moveaxis(hs, 0, 1).reshape(bsz, l, d)
    out = ctx.linear("slstm_down", hs.astype(h.dtype), p["down"])
    if return_state:
        return out, {"c": c, "n": n, "m": m, "h": hh}
    return out


def slstm_decode_init(cfg, batch: int):
    _, nh, dh = slstm_dims(cfg)
    z = jnp.zeros((batch, nh, dh), jnp.float32)
    return {"c": z, "n": z, "m": z - 1e30, "h": z}


def decode_state_bytes(cfg, btype: str) -> int:
    """Per-slot decode-state footprint (bytes) of one recurrent block.

    Unlike a KV cache this is O(1) in sequence length, which is exactly
    why the serving pool keeps recurrent state slot-indexed while KV is
    paged: admission control charges a request pages for its KV but a
    flat per-slot quantum for conv/SSM state.  Multiply by
    ``cfg.n_repeats`` (and pattern multiplicity) for the whole stack.
    """
    inits = {
        "mamba": lambda: mamba_decode_init(cfg, 1, cfg.cdtype),
        "mlstm": lambda: mlstm_decode_init(cfg, 1),
        "slstm": lambda: slstm_decode_init(cfg, 1),
    }
    if btype not in inits:
        raise ValueError(f"not a recurrent block type: {btype!r}")
    shapes = jax.eval_shape(inits[btype])
    return sum(math.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree.leaves(shapes))


def slstm_decode_step(cfg, p, ctx: cm.Ctx, h1, state):
    bsz = h1.shape[0]
    _, nh, dh = slstm_dims(cfg)
    x = ctx.linear("slstm_in", h1, p["w_in"]).astype(jnp.float32)[:, 0]
    step = _slstm_cell_step_factory(p, nh, dh)
    st = (state["c"], state["n"], state["m"], state["h"])
    (c, n, m, hh), h_out = step(st, x)
    out = ctx.linear("slstm_down",
                     h_out.reshape(bsz, 1, cfg.d_model).astype(h1.dtype),
                     p["down"])
    return out, {"c": c, "n": n, "m": m, "h": hh}
