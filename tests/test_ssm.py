"""Recurrent blocks: chunked-parallel training path == step-by-step decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import common as cm
from repro.models import ssm

KEY = jax.random.PRNGKey(0)
CTX = cm.Ctx(policy=cm.Policy(), compute_dtype=jnp.float32)


def _zamba_cfg():
    return dataclasses.replace(get_config("zamba2-2.7b", reduced=True),
                               compute_dtype="float32")


def _xlstm_cfg():
    return dataclasses.replace(get_config("xlstm-125m", reduced=True),
                               compute_dtype="float32")


def test_mamba_chunked_equals_decode_steps():
    cfg = _zamba_cfg()
    p = cm.unbox(ssm.init_mamba(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 16, cfg.d_model))
    y_par, final = ssm.apply_mamba(cfg, p, CTX, x, chunk=4,
                                   return_state=True)

    state = ssm.mamba_decode_init(cfg, 2, jnp.float32)
    ys = []
    for t in range(16):
        o, state = ssm.mamba_decode_step(cfg, p, CTX, x[:, t:t + 1], state)
        ys.append(o)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_par),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(state["ssm"]),
                               np.asarray(final["ssm"]), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_mamba_chunk_size_invariance(chunk):
    cfg = _zamba_cfg()
    p = cm.unbox(ssm.init_mamba(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(KEY, (1, 16, cfg.d_model))
    base = ssm.apply_mamba(cfg, p, CTX, x, chunk=16)
    got = ssm.apply_mamba(cfg, p, CTX, x, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=2e-3, atol=2e-3)


def test_mlstm_sequence_equals_decode_steps():
    cfg = _xlstm_cfg()
    p = cm.unbox(ssm.init_mlstm(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 12, cfg.d_model))
    y_par = ssm.apply_mlstm(cfg, p, CTX, x, chunk=4)

    state = ssm.mlstm_decode_init(cfg, 2)
    ys = []
    for t in range(12):
        o, state = ssm.mlstm_decode_step(cfg, p, CTX, x[:, t:t + 1], state)
        ys.append(o)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_par),
                               rtol=2e-3, atol=2e-3)


def _mlstm_token_serial(state, q, k, v, i_raw, f_raw):
    """Oracle: the one-token cell scanned over the sequence."""
    seq = lambda x: jnp.moveaxis(x, 1, 0)
    state, hs = jax.lax.scan(ssm._mlstm_cell_step, state,
                             tuple(map(seq, (q, k, v, i_raw, f_raw))))
    return state, jnp.moveaxis(hs, 0, 1)


def _mlstm_chunked(chunk):
    return lambda state, q, k, v, i_raw, f_raw: ssm._mlstm_chunkwise(
        state, q, k, v, i_raw, -jax.nn.softplus(-f_raw), chunk)


# gate regimes: (i_raw offset, f_raw offset); f_raw -8 forgets nearly all
# of the state each token, +8 keeps nearly all of it
GATES = {"mixed": (0.0, 0.0), "large_input": (20.0, 0.0),
         "forget_near_0": (0.0, -8.0), "forget_near_1": (0.0, 8.0)}


@pytest.mark.parametrize("gates", sorted(GATES))
def test_mlstm_chunkwise_equals_token_serial(gates):
    """Outputs, final (C, n, m) and every input's gradient agree with the
    token-serial cell, on a length that leaves a partial last chunk."""
    b, l, nh, dh, chunk = 2, 19, 3, 8, 8
    ks = jax.random.split(jax.random.fold_in(KEY, 7), 5)
    q, k, v = (jax.random.normal(kk, (b, l, nh, dh)) for kk in ks[:3])
    i_off, f_off = GATES[gates]
    i_raw = i_off + 3.0 * jax.random.normal(ks[3], (b, l, nh))
    f_raw = f_off + 3.0 * jax.random.normal(ks[4], (b, l, nh))
    state = (jnp.zeros((b, nh, dh, dh)), jnp.zeros((b, nh, dh)),
             jnp.full((b, nh), -1e30))
    args = (q, k, v, i_raw, f_raw)

    (c0, n0, m0), h0 = _mlstm_token_serial(state, *args)
    (c1, n1, m1), h1 = _mlstm_chunked(chunk)(state, *args)
    for got, want in ((h1, h0), (c1, c0), (n1, n0), (m1, m0)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def loss(fn):
        def f(*xs):
            (c, n, m), h = fn(state, *xs)
            return (jnp.sum(jnp.sin(h)) + 0.3 * jnp.sum(c) + jnp.sum(n)
                    + 0.1 * jnp.sum(m))
        return f

    g0 = jax.grad(loss(_mlstm_token_serial), argnums=range(5))(*args)
    g1 = jax.grad(loss(_mlstm_chunked(chunk)), argnums=range(5))(*args)
    # against a float64 oracle either form's f32 gradients err by up to
    # 3e-4 of the largest (large_input, forget_near_1)
    for name, got, want in zip("q k v i_raw f_raw".split(), g1, g0):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-3 * scale, err_msg=name)


@pytest.mark.parametrize("chunk", [4, 5, 8, 16])
def test_mlstm_chunk_size_invariance(chunk):
    cfg = _xlstm_cfg()
    p = cm.unbox(ssm.init_mlstm(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 16, cfg.d_model))
    base, st0 = ssm.apply_mlstm(cfg, p, CTX, x, chunk=16, return_state=True)
    got, st1 = ssm.apply_mlstm(cfg, p, CTX, x, chunk=chunk,
                               return_state=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-4, atol=1e-5)
    for key in ("c", "n", "m"):
        np.testing.assert_allclose(np.asarray(st1[key]),
                                   np.asarray(st0[key]), rtol=1e-4,
                                   atol=1e-5, err_msg=key)


@pytest.mark.parametrize("prefill", [5, 8])
def test_mlstm_prefill_then_decode(prefill):
    """Decoding continues from the state the chunkwise prefill returns."""
    cfg = _xlstm_cfg()
    p = cm.unbox(ssm.init_mlstm(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 12, cfg.d_model))
    y_all = ssm.apply_mlstm(cfg, p, CTX, x, chunk=4)
    y_pre, state = ssm.apply_mlstm(cfg, p, CTX, x[:, :prefill], chunk=4,
                                   return_state=True)
    ys = [y_pre]
    for t in range(prefill, 12):
        o, state = ssm.mlstm_decode_step(cfg, p, CTX, x[:, t:t + 1], state)
        ys.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)),
                               np.asarray(y_all), rtol=2e-3, atol=2e-3)


def test_slstm_sequence_equals_decode_steps():
    cfg = _xlstm_cfg()
    p = cm.unbox(ssm.init_slstm(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 12, cfg.d_model))
    y_par = ssm.apply_slstm(cfg, p, CTX, x, chunk=4)

    state = ssm.slstm_decode_init(cfg, 2)
    ys = []
    for t in range(12):
        o, state = ssm.slstm_decode_step(cfg, p, CTX, x[:, t:t + 1], state)
        ys.append(o)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_par),
                               rtol=2e-3, atol=2e-3)


def test_mamba_state_decay_bounded():
    """SSD decays are <= 1: states cannot blow up over long sequences."""
    cfg = _zamba_cfg()
    p = cm.unbox(ssm.init_mamba(cfg, KEY, jnp.float32))[0]
    x = jax.random.normal(KEY, (1, 64, cfg.d_model))
    _, st = ssm.apply_mamba(cfg, p, CTX, x, chunk=16, return_state=True)
    assert np.all(np.isfinite(np.asarray(st["ssm"])))
