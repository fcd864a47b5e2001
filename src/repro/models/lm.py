"""Decoder-only LM assembly: block stacking, embeddings, loss, decode.

The layer stack is organized as ``n_repeats`` repetitions of a small
``pattern`` unit (e.g. ("attn",) for dense models, ("mamba",)*5 +
("shared_attn",) for Zamba2, ("mlstm","slstm") for xLSTM).  Parameters of
patterned blocks carry a leading repeat axis and the whole stack runs
under one ``lax.scan``, which keeps compile time and HLO size flat in
depth — essential for the 512-device dry-runs.

Zamba2's ``shared_attn`` blocks share one parameter set across all
occurrences (the architecture's trick); they are closed over rather than
scanned.  Leading dense layers (DeepSeek-V3's ``first_k_dense_replace``,
``ArchConfig.first_dense_layers``) run once before the scan, their
parameters under ``params["dense"]``, stacked on a layers axis.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import common as cm
from repro.models import mlp as mlp_lib
from repro.models import ssm as ssm_lib


# ---------------------------------------------------------------------------
# Block init/apply dispatch
# ---------------------------------------------------------------------------

def _init_attn_core(cfg, key, dtype, prefix=""):
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": cm.dense_init(ks[0], (d, h * dh), ("embed", "qheads"), dtype),
        "wk": cm.dense_init(ks[1], (d, kvh * dh), ("embed", "kvheads"),
                            dtype),
        "wv": cm.dense_init(ks[2], (d, kvh * dh), ("embed", "kvheads"),
                            dtype),
        "wo": cm.dense_init(ks[3], (h * dh, d), ("qheads", "embed"), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = cm.zeros_init((h * dh,), ("qheads",), dtype)
        p["bk"] = cm.zeros_init((kvh * dh,), ("kvheads",), dtype)
        p["bv"] = cm.zeros_init((kvh * dh,), ("kvheads",), dtype)
    return p


def _init_mla_core(cfg, key, dtype):
    """Latent attention without a q low-rank (DeepSeek-V3 with
    ``q_lora_rank`` null): W_q, W_kva (latent c and the shared rope key),
    the latent's RMS norm, W_kvb (c -> per-head k_nope and v), W_o."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": cm.dense_init(ks[0], (d, h * cfg.qk_head_dim),
                            ("embed", "qheads"), dtype),
        "wkva": cm.dense_init(ks[1], (d, r + cfg.qk_rope_dim),
                              ("embed", None), dtype),
        "kv_norm": {"gamma": cm.ones_init((r,), (None,), dtype)},
        "wkvb": cm.dense_init(
            ks[2], (r, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
            (None, "qheads"), dtype),
        "wo": cm.dense_init(ks[3], (h * cfg.v_head_dim, d),
                            ("qheads", "embed"), dtype),
    }


def init_block(cfg, btype: str, key, dtype):
    ks = jax.random.split(key, 4)
    if btype in ("attn", "attn_moe", "shared_attn"):
        core = _init_mla_core if cfg.is_mla else _init_attn_core
        p = {"norm1": cm.init_norm(cfg, dtype),
             "attn": core(cfg, ks[0], dtype),
             "norm2": cm.init_norm(cfg, dtype)}
        if btype == "attn_moe":
            init_moe = (mlp_lib.init_moe_share if cfg.router == "sigmoid"
                        else mlp_lib.init_moe)
            p["moe"] = init_moe(cfg, ks[1], dtype)
        else:
            p["mlp"] = mlp_lib.init_mlp(cfg, ks[1], dtype)
        return p
    if btype == "mamba":
        return {"norm1": cm.init_norm(cfg, dtype),
                "mamba": ssm_lib.init_mamba(cfg, ks[0], dtype)}
    if btype == "mlstm":
        return {"norm1": cm.init_norm(cfg, dtype),
                "mlstm": ssm_lib.init_mlstm(cfg, ks[0], dtype)}
    if btype == "slstm":
        return {"norm1": cm.init_norm(cfg, dtype),
                "slstm": ssm_lib.init_slstm(cfg, ks[0], dtype)}
    raise ValueError(btype)


def _project_qkv(cfg, p, ctx, x, positions):
    b, s, _ = x.shape
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # shared sampling plan + single stored H' for q/k/v (they read the
    # same normed activation) — 3x fewer attention-input residuals
    q, k, v = ctx.linear_shared(
        ("attn_q", "attn_k", "attn_v"), x,
        [p["wq"], p["wk"], p["wv"]],
        biases=[p.get("bq"), p.get("bk"), p.get("bv")])
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kvh, dh)
    v = v.reshape(b, s, kvh, dh)
    if cfg.pos_mode == "rope":
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_mode == "mrope":
        q = cm.apply_mrope(q, positions, cfg.rope_theta)
        k = cm.apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _mla_qkv(cfg, p, ctx, x, positions):
    """MLA projections: q = x W_q -> (q_nope, q_pe) per head; [c, k_pe] =
    x W_kva (W_q and W_kva share one plan); c = RMSNorm(c); [k_nope, v]
    = c W_kvb per head; q_pe and the one-head k_pe rotated.  Returns q, k
    (B, S, H, nope + rope) and v (B, S, H, v_head_dim)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q, kva = ctx.linear_shared(("attn_q", "attn_kva"), x,
                               [p["wq"], p["wkva"]])
    q = q.reshape(b, s, h, dn + dr)
    c = cm.rms_norm(kva[..., :r], p["kv_norm"]["gamma"], cfg.norm_eps)
    kv = ctx.linear("attn_kvb", c, p["wkvb"]).reshape(b, s, h, dn + dv)
    rope = cm.apply_rope_interleaved if cfg.rope_interleave else cm.apply_rope
    q_pe = rope(q[..., dn:], positions, cfg.rope_theta)
    k_pe = rope(kva[..., r:].reshape(b, s, 1, dr), positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    return q, k, kv[..., dn:]


def _qkv(cfg, p, ctx, x, positions):
    if cfg.is_mla:
        return _mla_qkv(cfg, p, ctx, x, positions)
    return _project_qkv(cfg, p, ctx, x, positions)


def _ffn(cfg, btype, p, ctx, x):
    """The block's feed-forward: dense, capacity MoE or expert share."""
    if btype != "attn_moe":
        return mlp_lib.apply_mlp(cfg, p["mlp"], ctx, x), {}
    if cfg.router == "sigmoid":
        return mlp_lib.apply_moe_share(cfg, p["moe"], ctx, x)
    return mlp_lib.apply_moe(cfg, p["moe"], ctx, x)


def apply_block(cfg, btype: str, p, ctx: cm.Ctx, h, positions,
                shared=None) -> Tuple[jax.Array, Dict]:
    """Training/prefill application of one block.  h: (B, S, D)."""
    aux = {}
    rs = cfg.residual_scale
    if btype in ("attn", "attn_moe", "shared_attn"):
        if btype == "shared_attn":
            p = shared
        x = cm.apply_norm(cfg, p["norm1"], h)
        q, k, v = _qkv(cfg, p["attn"], ctx, x, positions)
        o = attn_lib.flash_attention(
            q, k, v, causal=True, q_block=ctx.policy.flash_block,
            kv_block=ctx.policy.flash_block, mode=ctx.policy.flash_mode)
        o = ctx.linear("attn_o", o.reshape(h.shape[0], h.shape[1], -1),
                       p["attn"]["wo"])
        h = h + rs * o
        x = cm.apply_norm(cfg, p["norm2"], h)
        m, aux = _ffn(cfg, btype, p, ctx, x)
        return h + rs * m, aux
    if btype == "mamba":
        x = cm.apply_norm(cfg, p["norm1"], h)
        return h + rs * ssm_lib.apply_mamba(cfg, p["mamba"], ctx, x), aux
    if btype == "mlstm":
        x = cm.apply_norm(cfg, p["norm1"], h)
        return h + rs * ssm_lib.apply_mlstm(cfg, p["mlstm"], ctx, x), aux
    if btype == "slstm":
        x = cm.apply_norm(cfg, p["norm1"], h)
        return h + rs * ssm_lib.apply_slstm(cfg, p["slstm"], ctx, x), aux
    raise ValueError(btype)


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def _stacked(init_one, keys):
    """Blocks stacked on a leading "layers" axis, one per key."""
    stacked = jax.vmap(init_one)(keys)
    # vmap stacks Boxed leaves; restore axes tuple with "layers"
    return jax.tree.map(
        lambda b: cm.Boxed(b.value, ("layers",) + tuple(b.axes)),
        stacked, is_leaf=lambda x: isinstance(x, cm.Boxed))


def _dense_ctx(cfg, ctx: cm.Ctx, i: int) -> cm.Ctx:
    """Leading dense layer i: the step key folded with n_repeats + i (the
    index after the scanned repeats'), tags under ``d{i}/``; the
    gradient-norm cache covers the scanned unit only."""
    return dataclasses.replace(ctx.fold(cfg.n_repeats + i),
                               tag_prefix=f"d{i}/", znorms=None)


def _dense_params(params, i: int):
    return jax.tree.map(lambda a: a[i], params["dense"])


def init_params(cfg: ArchConfig, key: jax.Array):
    """Returns the Boxed tree; use cm.unbox to split value/axes."""
    dtype = cfg.pdtype
    r = cfg.n_repeats
    keys = jax.random.split(key, len(cfg.pattern) + 4)

    unit = []
    shared = None
    for i, btype in enumerate(cfg.pattern):
        if btype == "shared_attn":
            if shared is None:
                shared = init_block(cfg, btype, keys[i], dtype)
            unit.append({})        # placeholder; params live in `shared`
        else:
            unit.append(_stacked(
                lambda kk, bt=btype: init_block(cfg, bt, kk, dtype),
                jax.random.split(keys[i], r)))

    params = {
        "embed": cm.dense_init(keys[-1], (cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), dtype, scale=0.02),
        "unit": tuple(unit),
        "final_norm": cm.init_norm(cfg, dtype),
    }
    if cfg.first_dense_layers:
        params["dense"] = _stacked(
            lambda kk: init_block(cfg, "attn", kk, dtype),
            jax.random.split(jax.random.fold_in(key, 1),
                             cfg.first_dense_layers))
    if shared is not None:
        params["shared"] = shared
    if not cfg.tie_embeddings:
        params["head"] = cm.dense_init(
            keys[-2], (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
            dtype)
    if cfg.family == "vlm":
        params["vis_proj"] = cm.dense_init(
            keys[-3], (cfg.d_model, cfg.d_model), ("embed", "embed"), dtype)
    if cfg.pos_mode == "learned":
        params["pos_embed"] = cm.dense_init(
            keys[-4], (cfg.max_learned_pos, cfg.d_model), (None, "embed"),
            dtype, scale=0.02)
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def embed_inputs(cfg, params, batch, ctx):
    """Token (+modality-stub) embedding.  Returns (h, positions)."""
    tokens = batch["tokens"]
    emb = params["embed"]
    h = jnp.take(emb, tokens, axis=0).astype(cfg.cdtype)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(cfg.cdtype)   # (B, S_vis, D) stub
        vis = ctx.linear("vis_proj", patches, params["vis_proj"])
        h = jnp.concatenate([vis, h], axis=1)
        positions = batch["positions3"]                 # (3, B, S)
    elif cfg.pos_mode == "learned":
        s = h.shape[1]
        h = h + params["pos_embed"][None, :s].astype(cfg.cdtype)
        positions = jnp.broadcast_to(jnp.arange(s)[None], h.shape[:2])
    else:
        s = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (h.shape[0], s))
    return h, positions


def forward(cfg: ArchConfig, params, batch, policy: cm.Policy,
            key: Optional[jax.Array] = None,
            znorms: Optional[Dict[str, jax.Array]] = None
            ) -> Tuple[jax.Array, Dict]:
    """Full forward to logits.  batch: {"tokens": (B,S), ...}."""
    ctx = cm.Ctx(policy=policy, key=key, znorms=None,
                 compute_dtype=cfg.cdtype)
    h, positions = embed_inputs(cfg, params, batch, ctx)
    shared = params.get("shared")
    for i in range(cfg.first_dense_layers):
        h, _ = apply_block(cfg, "attn", _dense_params(params, i),
                           _dense_ctx(cfg, ctx, i), h, positions)

    def unit_step(carry, xs):
        h, aux_lb = carry
        unit_params, ridx = xs
        ctx_r = ctx.fold(ridx)
        for j, btype in enumerate(cfg.pattern):
            sub = dataclasses.replace(ctx_r, tag_prefix=f"b{j}/", key=(
                None if ctx_r.key is None
                else jax.random.fold_in(ctx_r.key, j)))
            if znorms is not None:
                sub = dataclasses.replace(sub, znorms={
                    t: z[ridx] for t, z in znorms.items()})
            h, aux = apply_block(cfg, btype, unit_params[j], sub, h,
                                 positions, shared=shared)
            if "lb_loss" in aux:
                aux_lb = aux_lb + aux["lb_loss"]
        return (h, aux_lb), None

    if policy.remat != "none":
        unit_step = _remat_unit(unit_step, policy)

    ridx = jnp.arange(cfg.n_repeats)
    (h, lb), _ = jax.lax.scan(unit_step, (h, jnp.zeros((), jnp.float32)),
                              (params["unit"], ridx))
    h = cm.apply_norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"].T.astype(cfg.cdtype))
    else:
        logits = jnp.dot(h, params["head"].astype(cfg.cdtype))
    return logits, {"lb_loss": lb}


def _remat_unit(unit_step, policy: cm.Policy):
    if policy.remat == "full":
        return jax.checkpoint(unit_step)
    if policy.remat == "wtacrs_names":
        pol = jax.checkpoint_policies.save_only_these_names(
            "wtacrs_saved")
        return jax.checkpoint(unit_step, policy=pol)
    raise ValueError(policy.remat)


def lm_loss(cfg: ArchConfig, params, batch, policy: cm.Policy,
            key=None, znorms=None) -> Tuple[jax.Array, Dict]:
    """Next-token cross-entropy (labels = batch["labels"], -100 = masked)."""
    logits, aux = forward(cfg, params, batch, policy, key, znorms)
    labels = batch["labels"]
    if cfg.family == "vlm":
        # Only text positions carry labels; vision prefix is unsupervised.
        vis = logits.shape[1] - labels.shape[1]
        logits = logits[:, vis:]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if cfg.n_experts and cfg.router == "softmax":
        loss = loss + 0.01 * aux["lb_loss"] / cfg.n_layers
    aux["ce_loss"] = loss
    return loss, aux


# ---------------------------------------------------------------------------
# Prefill: forward + decode-state emission + last-token logits
# ---------------------------------------------------------------------------

def prefill(cfg: ArchConfig, params, batch, policy: cm.Policy):
    """Run the prompt through the stack, returning (last_logits, states).

    states match decode_state_init's layout with max_len == prompt length
    (the serving layer allocates head-room by padding the KV axis).
    """
    ctx = cm.Ctx(policy=policy, key=None, znorms=None,
                 compute_dtype=cfg.cdtype)
    h, positions = embed_inputs(cfg, params, batch, ctx)
    shared = params.get("shared")
    s = h.shape[1]

    def attn_prefill(btype, p, ctx_r, h):
        x = cm.apply_norm(cfg, p["norm1"], h)
        q, k, v = _qkv(cfg, p["attn"], ctx_r, x, positions)
        o = attn_lib.flash_attention(
            q, k, v, causal=True, q_block=ctx_r.policy.flash_block,
            kv_block=ctx_r.policy.flash_block,
            mode=ctx_r.policy.flash_mode)
        o = ctx_r.linear("attn_o", o.reshape(h.shape[0], s, -1),
                         p["attn"]["wo"])
        h = h + cfg.residual_scale * o
        x = cm.apply_norm(cfg, p["norm2"], h)
        m, _ = _ffn(cfg, btype, p, ctx_r, x)
        return (h + cfg.residual_scale * m,
                {"k": k.astype(cfg.cdtype), "v": v.astype(cfg.cdtype)})

    dense_states = []
    for i in range(cfg.first_dense_layers):
        h, st = attn_prefill("attn", _dense_params(params, i),
                             _dense_ctx(cfg, ctx, i), h)
        dense_states.append(st)

    def unit_step(h, xs):
        unit_params, ridx = xs
        ctx_r = ctx.fold(ridx)
        states = []
        for j, btype in enumerate(cfg.pattern):
            p = shared if btype == "shared_attn" else unit_params[j]
            x = cm.apply_norm(cfg, p["norm1"], h)
            if btype in ("attn", "attn_moe", "shared_attn"):
                h, st = attn_prefill(btype, p, ctx_r, h)
                states.append(st)
            elif btype == "mamba":
                o, st = ssm_lib.apply_mamba(cfg, p["mamba"], ctx_r, x,
                                            return_state=True)
                h = h + cfg.residual_scale * o
                states.append(st)
            elif btype == "mlstm":
                o, st = ssm_lib.apply_mlstm(cfg, p["mlstm"], ctx_r, x,
                                            return_state=True)
                h = h + cfg.residual_scale * o
                states.append(st)
            elif btype == "slstm":
                o, st = ssm_lib.apply_slstm(cfg, p["slstm"], ctx_r, x,
                                            return_state=True)
                h = h + cfg.residual_scale * o
                states.append(st)
        return h, tuple(states)

    ridx = jnp.arange(cfg.n_repeats)
    h, states = jax.lax.scan(unit_step, h, (params["unit"], ridx))
    if dense_states:
        states = states + (jax.tree.map(lambda *a: jnp.stack(a),
                                        *dense_states),)
    h = cm.apply_norm(cfg, params["final_norm"], h[:, -1:])
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"].T.astype(cfg.cdtype))
    else:
        logits = jnp.dot(h, params["head"].astype(cfg.cdtype))
    return logits[:, 0], states


# ---------------------------------------------------------------------------
# Decode (single-token serve step with per-block state)
# ---------------------------------------------------------------------------

def block_decode_init(cfg, btype, batch_size, max_len):
    """Decode state for ONE block type, un-stacked (no repeat axis).

    Attention blocks get a (B, max_len, KVH, Dh) KV cache (MLA: k and
    v expanded per head, (H, nope + rope) and (H, v_head_dim)); recurrent
    blocks get their O(1) per-sequence state.  The serving slot pool
    builds its per-block pools from this (KV paged, SSM slot-indexed),
    so it is the public per-block counterpart of ``decode_state_init``.
    """
    kvh, dh, dv = cfg.n_kv_heads, cfg.head_dim, cfg.head_dim
    if cfg.is_mla:
        kvh, dh, dv = cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim
    if btype in ("attn", "attn_moe", "shared_attn"):
        return {
            "k": jnp.zeros((batch_size, max_len, kvh, dh), cfg.cdtype),
            "v": jnp.zeros((batch_size, max_len, kvh, dv), cfg.cdtype),
        }
    if btype == "mamba":
        return ssm_lib.mamba_decode_init(cfg, batch_size, cfg.cdtype)
    if btype == "mlstm":
        return ssm_lib.mlstm_decode_init(cfg, batch_size)
    if btype == "slstm":
        return ssm_lib.slstm_decode_init(cfg, batch_size)
    raise ValueError(btype)


_block_decode_init = block_decode_init  # historical private name


def decode_state_init(cfg: ArchConfig, batch_size: int, max_len: int):
    """Stacked (over repeats) decode state for every block in the unit;
    with leading dense layers, then their states stacked over them."""
    states = []
    stacks = [(b, cfg.n_repeats) for b in cfg.pattern]
    if cfg.first_dense_layers:
        stacks.append(("attn", cfg.first_dense_layers))
    for btype, n in stacks:
        one = _block_decode_init(cfg, btype, batch_size, max_len)
        stacked = jax.tree.map(
            lambda x, n=n: jnp.broadcast_to(x[None], (n,) + x.shape), one)
        states.append(stacked)
    return tuple(states)


def _attn_decode(cfg, p, ctx, h1, state, pos):
    """h1: (B,1,D); state: {k,v} caches; pos: (B,) per-row positions."""
    b = h1.shape[0]
    x = cm.apply_norm(cfg, p["norm1"], h1)
    positions = pos[:, None]
    if cfg.pos_mode == "mrope":
        positions = jnp.broadcast_to(pos[None, :, None], (3, b, 1))
    q, k, v = _qkv(cfg, p["attn"], ctx, x, positions)
    rows = jnp.arange(b)
    kc = state["k"].at[rows, pos].set(k[:, 0].astype(cfg.cdtype))
    vc = state["v"].at[rows, pos].set(v[:, 0].astype(cfg.cdtype))
    o = attn_lib.decode_attention(q, kc, vc, pos + 1)
    o = ctx.linear("attn_o", o.reshape(b, 1, -1), p["attn"]["wo"])
    h1 = h1 + cfg.residual_scale * o
    x = cm.apply_norm(cfg, p["norm2"], h1)
    m, _ = _ffn(cfg, "attn_moe" if "moe" in p else "attn", p, ctx, x)
    return h1 + cfg.residual_scale * m, {"k": kc, "v": vc}


def decode_step(cfg: ArchConfig, params, token: jax.Array, pos: jax.Array,
                states, policy: cm.Policy):
    """One serve step: token (B,) int32 -> logits (B, V), new states.

    ``pos`` is a scalar (every row at the same position — the classic
    aligned-batch serve step) or a (B,) vector of per-row positions —
    the continuous-batching case, where each slot of a ragged batch
    writes its KV at its own offset and attends over its own prefix
    length.  The scalar case is lowered through the identical vector
    ops (broadcast), so both paths share one set of numerics.
    """
    ctx = cm.Ctx(policy=policy, key=None, znorms=None,
                 compute_dtype=cfg.cdtype)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                           token.shape)
    h = jnp.take(params["embed"], token, axis=0)[:, None, :].astype(
        cfg.cdtype)
    if cfg.pos_mode == "learned":
        h = h + jnp.take(params["pos_embed"], pos, axis=0)[:, None].astype(
            cfg.cdtype)
    shared = params.get("shared")
    dense_states = []
    if cfg.first_dense_layers:
        states, dense = states[:-1], states[-1]
        for i in range(cfg.first_dense_layers):
            h, st = _attn_decode(cfg, _dense_params(params, i), ctx, h,
                                 jax.tree.map(lambda a: a[i], dense), pos)
            dense_states.append(st)

    def unit_step(h, xs):
        unit_params, unit_state, ridx = xs
        new_states = []
        for j, btype in enumerate(cfg.pattern):
            p = shared if btype == "shared_attn" else unit_params[j]
            st = unit_state[j]
            if btype in ("attn", "attn_moe", "shared_attn"):
                h, st = _attn_decode(cfg, p, ctx, h, st, pos)
            elif btype == "mamba":
                x = cm.apply_norm(cfg, p["norm1"], h)
                o, st = ssm_lib.mamba_decode_step(cfg, p["mamba"], ctx, x,
                                                  st)
                h = h + cfg.residual_scale * o
            elif btype == "mlstm":
                x = cm.apply_norm(cfg, p["norm1"], h)
                o, st = ssm_lib.mlstm_decode_step(cfg, p["mlstm"], ctx, x,
                                                  st)
                h = h + cfg.residual_scale * o
            elif btype == "slstm":
                x = cm.apply_norm(cfg, p["norm1"], h)
                o, st = ssm_lib.slstm_decode_step(cfg, p["slstm"], ctx, x,
                                                  st)
                h = h + cfg.residual_scale * o
            new_states.append(st)
        return h, tuple(new_states)

    ridx = jnp.arange(cfg.n_repeats)
    h, new_states = jax.lax.scan(unit_step, h,
                                 (params["unit"], states, ridx))
    if dense_states:
        new_states = new_states + (jax.tree.map(lambda *a: jnp.stack(a),
                                                *dense_states),)
    h = cm.apply_norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"].T.astype(cfg.cdtype))
    else:
        logits = jnp.dot(h, params["head"].astype(cfg.cdtype))
    return logits[:, 0], new_states
