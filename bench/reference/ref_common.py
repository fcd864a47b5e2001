"""Plain reference pieces shared by the reference models.

Written from the paper (WTA-CRS, Eq. 3 and Eq. 6 with |C| from Theorem 2)
and from the program's documented conventions (per-sample plans over the
token rows, p proportional to the row norm of H, the PRNG key of a
linear folded from the step key by layer, block and tag), and importing
nothing of the program.  Every contraction goes through a ``Precision``:
``f32`` is float32 at the highest matmul precision (the reference);
``fp8`` casts both operands of every contraction to float8 e4m3 with a
per-tensor scale (the control, one precision below the configuration's
bfloat16 compute).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_EPS = 1e-30
_FP8_MAX = 448.0


class Precision:
    def __init__(self, mode: str):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def q(self, x):
        x = x.astype(jnp.float32)
        if self.mode == "f32":
            return x
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def einsum(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST)

    def __hash__(self):
        return hash(self.mode)

    def __eq__(self, other):
        return isinstance(other, Precision) and other.mode == self.mode


def tag_seed(tag: str) -> int:
    return zlib.crc32(tag.encode()) & 0x7FFFFFFF


def rms_norm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


# ---------------------------------------------------------------------------
# WTA-CRS plan (Eq. 6) and the sampled linear
# ---------------------------------------------------------------------------

def wta_plan(p, k: int, key):
    """p (m,) probabilities -> idx (k,), scale (k,).  The |C| largest
    atoms enter with scale 1 (|C| minimizes (1 - sum_C p) / (k - |C|),
    at most k - 1); k - |C| iid draws from the tail carry scale
    (1 - sum_C p) / ((k - |C|) p_j)."""
    m = p.shape[0]
    order = jnp.argsort(-p)
    csum = jnp.cumsum(p[order])
    cs = jnp.arange(k)
    top = jnp.where(cs == 0, 0.0, csum[jnp.maximum(cs - 1, 0)])
    c = jnp.argmin((1.0 - top) / (k - cs).astype(p.dtype)).astype(jnp.int32)
    det_mass = jnp.where(c == 0, 0.0, csum[jnp.maximum(c - 1, 0)])
    resid = jnp.maximum(1.0 - det_mass, 0.0)
    rank = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    logits = jnp.where(rank >= c, jnp.log(jnp.maximum(p, _EPS)), -jnp.inf)
    drawn = jax.random.categorical(key, logits, shape=(k,)).astype(jnp.int32)
    slots = jnp.arange(k, dtype=jnp.int32)
    det = slots < c
    idx = jnp.where(det, order[jnp.minimum(slots, m - 1)], drawn)
    n_stoc = jnp.maximum(k - c, 1).astype(p.dtype)
    scale = jnp.where(det, 1.0,
                      resid / (n_stoc * jnp.maximum(p[drawn], _EPS)))
    return idx, scale.astype(jnp.float32)


def _plans(h, keys, k):
    norms = jnp.sqrt(jnp.sum(jnp.square(h), axis=-1))          # (b, S)
    tot = jnp.sum(norms, axis=-1, keepdims=True)
    p = jnp.where(tot > 0, norms / jnp.maximum(tot, _EPS),
                  1.0 / norms.shape[-1])
    return jax.vmap(lambda pr, kd: wta_plan(
        pr, k, jax.random.wrap_key_data(kd)))(p, keys)


def _rows(x, idx):
    return jax.vmap(lambda xb, ib: jnp.take(xb, ib, axis=0))(x, idx)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def sampled_linears(h, ws, keys, k, prec):
    """Exact outputs h @ w for each w; each dW estimated from k rows per
    sample under one shared plan: dW = sum_b H'_b^T (dZ_b[idx] * scale)."""
    return tuple(prec.einsum("bsd,de->bse", h, w) for w in ws)


def _sl_fwd(h, ws, keys, k, prec):
    idx, scale = _plans(h, keys, k)
    return (sampled_linears(h, ws, keys, k, prec),
            (_rows(h, idx), idx, scale, ws, keys))


def _sl_bwd(k, prec, res, dzs):
    h_sub, idx, scale, ws, keys = res
    dh = sum(prec.einsum("bse,de->bsd", dz, w) for dz, w in zip(dzs, ws))
    dws = tuple(prec.einsum("bkd,bke->de", h_sub,
                            _rows(dz, idx) * scale[..., None])
                for dz in dzs)
    return dh, dws, jnp.zeros_like(keys)


sampled_linears.defvjp(_sl_fwd, _sl_bwd)


class Linears:
    """Routes a block's linears: exact, or WTA-CRS with the program's key
    convention.  ``key`` is the block's key data (None = exact);
    ``rows`` (r0, B) picks this row block's per-sample keys out of the
    B-way split."""

    def __init__(self, prec, budget, key, rows, prefix):
        self.prec, self.budget, self.key = prec, budget, key
        self.rows, self.prefix = rows, prefix

    def k_rows(self, s):
        return min(max(8, int(round(self.budget * s))), s)

    def __call__(self, tags, h, ws):
        s = h.shape[1]
        if self.key is None or self.budget is None or self.k_rows(s) >= s:
            return tuple(self.prec.einsum("bsd,de->bse", h, w) for w in ws)
        tag = "+".join(self.prefix + t for t in tags)
        lk = jax.random.fold_in(jax.random.wrap_key_data(self.key),
                                tag_seed(tag))
        r0, b_all = self.rows
        keys = jax.random.key_data(jax.random.split(lk, b_all))
        keys = jax.lax.dynamic_slice_in_dim(keys, r0, h.shape[0])
        return sampled_linears(h, tuple(ws), keys, self.k_rows(s), self.prec)

    def one(self, tag, h, w):
        return self(tuple([tag]), h, [w])[0]


def block_key(step_key, ridx, j):
    """Key data of block j of repeat ridx (None when exact)."""
    if step_key is None:
        return None
    k = jax.random.fold_in(jax.random.wrap_key_data(step_key), ridx)
    return jax.random.key_data(jax.random.fold_in(k, j))


def nll_sum(logits, labels):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def adamw(params, grads, m, v, count, lr, b1=0.9, b2=0.999, eps=1e-8):
    count = count + 1
    bc1 = 1.0 - b1 ** count
    bc2 = 1.0 - b2 ** count
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, a, b: p - lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps),
        params, m, v)
    return params, m, v, count
