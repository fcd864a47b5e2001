"""GQA attention: flash-style training path, cached decode path.

The training/prefill path is a pure-JAX flash attention (online softmax
over KV blocks, scan-structured so the HLO stays compact and activation
memory is O(S * block) instead of O(S^2)).  Sequence lengths up to 32k
prefill compile and fit on a v5e this way.

``mode="full"`` visits every (q-block, kv-block) pair and masks; the
causal half of the pairs is wasted compute.  ``mode="triangular"``
(a perf-iteration, see EXPERIMENTS.md §Perf) walks only the lower
triangle of block pairs with a static flattened pair list, halving
attention FLOPs at identical numerics.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import spans

NEG_INF = -1e30


def _block_pairs(nq: int, nk: int, causal: bool):
    """Static (qi, kj) visit order for triangular mode, grouped by qi."""
    pairs = []
    for i in range(nq):
        kmax = min(i + 1, nk) if causal else nk
        for j in range(kmax):
            pairs.append((i, j, j == kmax - 1))
    return pairs


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_block: int = 512,
                    kv_block: int = 512, mode: str = "full",
                    q_offset: int = 0) -> jax.Array:
    """q, k: (B, Sq|Skv, H|KVH, Dh); v: (B, Skv, KVH, Dv).  Returns
    (B, Sq, H, Dv); scores are scaled by 1/sqrt(Dh), the q/k width (MLA
    has Dv != Dh).

    ``q_offset``: absolute position of q[0] (for chunked prefill).
    """
    with jax.named_scope(spans.ATTENTION):
        b, sq, h, dh = q.shape
        skv, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
        g = h // kvh
        q_block = min(q_block, sq)
        kv_block = min(kv_block, skv)
        assert sq % q_block == 0 and skv % kv_block == 0
        nq, nk = sq // q_block, skv // kv_block
        scale = 1.0 / math.sqrt(dh)

        qr = q.reshape(b, nq, q_block, kvh, g, dh)
        qr = jnp.moveaxis(qr, 1, 0)                 # (nq, B, bq, KVH, G, Dh)
        kr = jnp.moveaxis(k.reshape(b, nk, kv_block, kvh, dh), 1, 0)
        vr = jnp.moveaxis(v.reshape(b, nk, kv_block, kvh, dv), 1, 0)

        q_pos_base = jnp.arange(q_block) + q_offset
        k_pos_base = jnp.arange(kv_block)

        # Flash memory profile under AD: rematerialize the block probability
        # matrices in the backward pass (this is what makes it "flash" — an
        # un-rematted scan would store every (bq, bk) p-block, O(S^2) again).
        @jax.checkpoint
        def attend_block(qc, kc, vc, qi, kj, m, l, acc):
            # qc: (B,bq,KVH,G,Dh) kc/vc: (B,bk,KVH,Dh)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            if causal:
                qpos = q_pos_base + qi * q_block
                kpos = k_pos_base + kj * kv_block
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            # probabilities ride in the input dtype (bf16 on the TPU path):
            # halves the dominant p-block HBM traffic; softmax stats stay f32
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(qc.dtype),
                            vc).astype(jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return m_new, l_new, acc_new

        def init_state():
            m = jnp.full((b, kvh, g, q_block), NEG_INF, jnp.float32)
            l = jnp.zeros((b, kvh, g, q_block), jnp.float32)
            acc = jnp.zeros((b, kvh, g, q_block, dv), jnp.float32)
            return m, l, acc

        if mode == "triangular":
            pairs = _block_pairs(nq, nk, causal)
            qi_arr = jnp.array([p[0] for p in pairs], jnp.int32)
            kj_arr = jnp.array([p[1] for p in pairs], jnp.int32)
            last_arr = jnp.array([p[2] for p in pairs], jnp.bool_)

            out0 = jnp.zeros((nq, b, kvh, g, q_block, dv), jnp.float32)

            def step(carry, xs):
                m, l, acc, out = carry
                qi, kj, is_last = xs
                qc = qr[qi]
                kc, vc = kr[kj], vr[kj]
                m, l, acc = attend_block(qc, kc, vc, qi, kj, m, l, acc)
                o = acc / jnp.maximum(l, 1e-30)[..., None]
                out = jax.lax.cond(
                    is_last, lambda o_: jax.lax.dynamic_update_slice(
                        out, o[None], (qi, 0, 0, 0, 0, 0)),
                    lambda o_: out, o)
                m0, l0, acc0 = init_state()
                m = jnp.where(is_last, m0, m)
                l = jnp.where(is_last, l0, l)
                acc = jnp.where(is_last, acc0, acc)
                return (m, l, acc, out), None

            (m, l, acc, out), _ = jax.lax.scan(
                step, init_state() + (out0,), (qi_arr, kj_arr, last_arr))
            o = out                                      # (nq,B,KVH,G,bq,Dh)
        else:
            @jax.checkpoint
            def q_row(qc, qi):
                def kv_step(carry, kblk):
                    kc, vc, kj = kblk
                    m, l, acc = carry
                    return attend_block(qc, kc, vc, qi, kj, m, l, acc), None

                (m, l, acc), _ = jax.lax.scan(
                    kv_step, init_state(),
                    (kr, vr, jnp.arange(nk, dtype=jnp.int32)))
                return acc / jnp.maximum(l, 1e-30)[..., None]

            def q_step(_, qblk):
                qc, qi = qblk
                return None, q_row(qc, qi)

            _, o = jax.lax.scan(q_step, None,
                                (qr, jnp.arange(nq, dtype=jnp.int32)))

        # (nq, B, KVH, G, bq, Dv) -> (B, Sq, H, Dv)
        o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 4, 2, 3, 5)
        o = o.reshape(b, sq, h, dv)
        return o.astype(q.dtype)


def attention_reference(q, k, v, *, causal=True, q_offset: int = 0):
    """O(S^2)-memory oracle for flash_attention (tests only)."""
    b, sq, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qr = q.reshape(b, sq, kvh, g, dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k.astype(jnp.float32))
    s = s / math.sqrt(dh)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, v.shape[-1])
    return o.astype(q.dtype)


def decode_attention(q1: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array) -> jax.Array:
    """Single-token attention against a (B, Smax, KVH, Dh) KV cache.

    q1: (B, 1, H, Dh); the v cache's last dim may differ from Dh (MLA).
    cache_len: scalar or (B,) number of valid positions
    (the new token's K/V must already be written at cache_len-1).

    Numerics mirror ``flash_attention``'s block step exactly: scores and
    softmax statistics in f32, UNNORMALIZED probabilities rounded to the
    cache dtype before the PV product, normalization by l afterwards.
    The earlier formulation (f32 softmax, f32 PV) was mathematically
    equivalent but rounded differently from the training/teacher-forced
    path — in bf16 the O(eps) drift was enough to flip near-tied MoE
    router top-k decisions between decode and forward, which showed up as
    rare ~1.5-magnitude logit divergences on dbrx (the decode-consistency
    failure formerly deselected in CI).  With matched rounding, cached
    decode bit-matches the forward pass whenever the context fits one KV
    block (masked positions contribute exp(NEG_INF - m) == 0 exactly).
    """
    b, _, h, dh = q1.shape
    smax, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(dh)
    qr = q1.reshape(b, kvh, g, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qr,
                   k_cache.astype(jnp.float32)) * scale
    valid = jnp.arange(smax)[None] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgk,bkhd->bhgd", p.astype(q1.dtype),
                    v_cache).astype(jnp.float32)
    o = pv / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(b, 1, h, v_cache.shape[-1]).astype(q1.dtype)
