"""Latent attention (MLA) and the whole Kanana-2 share against the plain
reference in ``bench/reference/deepseek_v3.py``: flash attention with a
v width apart from the q/k width, the MLA block, and one step's loss and
gradients, exact and WTA-CRS on the same keys."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import EstimatorKind, WTACRSConfig
from repro.models import attention as attn_lib
from repro.models import common as cm
from repro.models import lm

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "reference"))
import deepseek_v3 as ref  # noqa: E402
from ref_common import Linears, Precision, nll_sum  # noqa: E402

KEY = jax.random.PRNGKey(3)
F32 = Precision("f32")


def _cfg():
    cfg = get_config("kanana-2-30b-a3b", reduced=True)
    return dataclasses.replace(cfg, compute_dtype="float32")


def _cfgd(cfg):
    d = dataclasses.asdict(cfg)
    d["pattern"] = list(d["pattern"])
    return d


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("mode", ["full", "triangular"])
def test_flash_attention_with_v_width_apart(mode):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (2, 64, 4, 24))
    k = jax.random.normal(k2, (2, 64, 4, 24))
    v = jax.random.normal(k3, (2, 64, 4, 16))
    got = attn_lib.flash_attention(q, k, v, q_block=16, kv_block=16,
                                   mode=mode)
    want = attn_lib.attention_reference(q, k, v)
    assert got.shape == (2, 64, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # scaled by the q/k width: a wider v changes nothing of the scores
    v2 = jnp.concatenate([v, v], -1)
    got2 = attn_lib.flash_attention(q, k, v2, q_block=16, kv_block=16,
                                    mode=mode)
    np.testing.assert_allclose(np.asarray(got2[..., :16]), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


def test_interleaved_rope_rotates_adjacent_pairs():
    x = jax.random.normal(KEY, (1, 5, 2, 8))
    pos = jnp.arange(5)[None]
    got = cm.apply_rope_interleaved(x, pos, 1e6)
    # pair i of position t turns by t * theta^(-2i/8)
    ang = np.arange(5)[:, None] * 1e6 ** (-np.arange(0, 8, 2) / 8)
    a, b = np.asarray(x)[0, ..., 0::2], np.asarray(x)[0, ..., 1::2]
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(np.asarray(got)[0, ..., 0::2], a * c - b * s,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got)[0, ..., 1::2], a * s + b * c,
                               rtol=1e-5, atol=1e-6)


def test_mla_block_matches_reference():
    """The leading dense layer (MLA + SwiGLU) on the same weights."""
    cfg = _cfg()
    params = ref.init(_cfgd(cfg), KEY)
    p0 = jax.tree.map(lambda a: a[0], params["dense"])
    h = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    ctx = cm.Ctx(policy=cm.Policy(), compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = lm.apply_block(cfg, "attn", p0, ctx, h, pos)
        want = ref._layer(_cfgd(cfg), p0, h,
                          Linears(F32, None, None, (0, 2), "d0/"), F32)
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("budget", [None, 0.3], ids=["exact", "wta_crs"])
def test_program_matches_reference_loss_and_grads(budget):
    """One step's loss and every leaf's gradient, program against the
    plain reference, from the same weights, batch and step key."""
    cfg = _cfg()
    cfgd = _cfgd(cfg)
    params = ref.init(cfgd, KEY)
    b, s = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, s), 0,
                              cfg.vocab_size)
    labels = jnp.roll(toks, -1, 1)
    step_key = jax.random.fold_in(jax.random.PRNGKey(9), 0)
    pol = (cm.Policy() if budget is None else cm.Policy(
        wtacrs=WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=budget)))

    def prog(p):
        return lm.lm_loss(cfg, p, {"tokens": toks, "labels": labels}, pol,
                          key=step_key)[0]

    def plain(p):
        lg = ref.logits(cfgd, p, toks, F32, budget=budget,
                        step_key=jax.random.key_data(step_key))
        return nll_sum(lg, labels) / (b * s)

    with jax.default_matmul_precision("highest"):
        l1, g1 = jax.value_and_grad(prog)(params)
        l2, g2 = jax.value_and_grad(plain)(params)
    assert abs(float(l1) - float(l2)) < 1e-5 * abs(float(l2))
    flat = jax.tree_util.tree_flatten_with_path(g1)[0]
    for (path, a), c in zip(flat, jax.tree.leaves(g2)):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(np.asarray(a)) and not np.any(np.asarray(c))
            continue
        assert _rel(a, c) < 1e-3, name
