"""The WTA-CRS linear layer: gradient semantics, tap, LoRA composition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LoRAConfig, init_lora_params, lora_linear,
                        read_grad_norm_tap, wtacrs_linear)
from repro.core.config import WTACRSConfig
from repro.core.kernel_config import KernelConfig


@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (4, 32, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 48)) * 0.1
    return h, w


def test_forward_is_exact(setup):
    """The approximation lives only in the backward pass (Sec. 3.2)."""
    h, w = setup
    z = wtacrs_linear(h, w, key=jax.random.PRNGKey(1),
                      cfg=WTACRSConfig(budget=0.25, min_rows=4))
    np.testing.assert_allclose(np.asarray(z),
                               np.asarray(jnp.einsum("bsd,de->bse", h, w)),
                               rtol=2e-5, atol=2e-5)


def test_dh_is_exact(setup):
    h, w = setup
    cfg = WTACRSConfig(budget=0.25, min_rows=4)

    def f(hh):
        return jnp.sum(jnp.sin(wtacrs_linear(
            hh, w, key=jax.random.PRNGKey(3), cfg=cfg)))

    def f_exact(hh):
        return jnp.sum(jnp.sin(jnp.einsum("bsd,de->bse", hh, w)))

    np.testing.assert_allclose(np.asarray(jax.grad(f)(h)),
                               np.asarray(jax.grad(f_exact)(h)),
                               rtol=2e-4, atol=2e-4)


def test_dw_unbiased(setup):
    h, w = setup
    cfg = WTACRSConfig(budget=0.25, min_rows=4)

    def f(ww, key):
        return jnp.sum(jnp.sin(wtacrs_linear(h, ww, key=key, cfg=cfg)))

    def f_exact(ww):
        return jnp.sum(jnp.sin(jnp.einsum("bsd,de->bse", h, ww)))

    g_exact = jax.grad(f_exact)(w)
    keys = jax.random.split(jax.random.PRNGKey(5), 2500)
    gs = jax.vmap(lambda k: jax.grad(f)(w, k))(keys)
    g_mean = jnp.mean(gs, axis=0)
    rel = float(jnp.linalg.norm(g_mean - g_exact)
                / jnp.linalg.norm(g_exact))
    assert rel < 0.08


def test_budget_one_equals_exact_grad(setup):
    h, w = setup
    cfg = WTACRSConfig(budget=1.0)

    def f(ww):
        return jnp.sum(jnp.sin(wtacrs_linear(
            h, ww, key=jax.random.PRNGKey(0), cfg=cfg)))

    def f_exact(ww):
        return jnp.sum(jnp.sin(jnp.einsum("bsd,de->bse", h, ww)))

    np.testing.assert_allclose(np.asarray(jax.grad(f)(w)),
                               np.asarray(jax.grad(f_exact)(w)),
                               rtol=2e-4, atol=2e-4)


def test_grad_norm_tap_returns_dz_norms(setup):
    h, w = setup
    cfg = WTACRSConfig(budget=0.25, min_rows=4)
    znorm = jnp.ones(h.shape[:2])

    def f(ww, zn):
        return jnp.sum(jnp.sin(wtacrs_linear(
            h, ww, key=jax.random.PRNGKey(7), znorm=zn, cfg=cfg)))

    gz = jax.grad(f, argnums=1)(w, znorm)
    dz = jnp.cos(jnp.einsum("bsd,de->bse", h, w))
    np.testing.assert_allclose(np.asarray(read_grad_norm_tap(gz)),
                               np.asarray(jnp.linalg.norm(dz, axis=-1)),
                               rtol=1e-4, atol=1e-4)


def test_cached_znorm_changes_sampling_but_stays_unbiased(setup):
    h, w = setup
    cfg = WTACRSConfig(budget=0.25, min_rows=4)
    znorm = jax.random.uniform(jax.random.PRNGKey(11), h.shape[:2]) + 0.1

    def f(ww, key):
        return jnp.sum(jnp.sin(wtacrs_linear(h, ww, key=key, znorm=znorm,
                                             cfg=cfg)))

    def f_exact(ww):
        return jnp.sum(jnp.sin(jnp.einsum("bsd,de->bse", h, ww)))

    g_exact = jax.grad(f_exact)(w)
    keys = jax.random.split(jax.random.PRNGKey(12), 2500)
    gs = jax.vmap(lambda k: jax.grad(f)(w, k))(keys)
    rel = float(jnp.linalg.norm(jnp.mean(gs, 0) - g_exact)
                / jnp.linalg.norm(g_exact))
    assert rel < 0.08


def test_2d_input_supported():
    h = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    z = wtacrs_linear(h, w, key=jax.random.PRNGKey(2),
                      cfg=WTACRSConfig(budget=0.5, min_rows=4))
    assert z.shape == (64, 8)


def test_lora_only_adapters_receive_grads(setup):
    h, w = setup
    lcfg = LoRAConfig(rank=4, enabled=True)
    lp = init_lora_params(jax.random.PRNGKey(0), 64, 48, 4)
    # B starts at zero (adapter == identity); make it nonzero so gradient
    # flows to A as well
    lp["lora_b"] = jax.random.normal(jax.random.PRNGKey(2), (4, 48)) * 0.1

    def f(params):
        ww, ap = params
        z = lora_linear(h, ww, ap["lora_a"], ap["lora_b"], lcfg,
                        key=jax.random.PRNGKey(1),
                        cfg=WTACRSConfig(budget=0.5, min_rows=4))
        return jnp.sum(z * z)

    gw, ga = jax.grad(f)((w, lp))
    assert float(jnp.max(jnp.abs(gw))) == 0.0          # base frozen
    assert float(jnp.max(jnp.abs(ga["lora_a"]))) > 0.0
    assert float(jnp.max(jnp.abs(ga["lora_b"]))) > 0.0


def test_lora_zero_b_init_is_identity(setup):
    h, w = setup
    lcfg = LoRAConfig(rank=4, enabled=True)
    lp = init_lora_params(jax.random.PRNGKey(0), 64, 48, 4)
    z = lora_linear(h, w, lp["lora_a"], lp["lora_b"], lcfg,
                    key=jax.random.PRNGKey(1),
                    cfg=WTACRSConfig(budget=1.0))
    np.testing.assert_allclose(np.asarray(z),
                               np.asarray(jnp.einsum("bsd,de->bse", h, w)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [1, 2, 8])
def test_use_kernel_matches_jnp_path(batch, dtype):
    """The ``pallas`` and ``jnp`` backends differ only in the plan's
    row-norm pass (the Pallas kernel, interpret mode on CPU, or an
    einsum); both then run the same gather and dot_general dW, so their
    weight gradients agree for all batch sizes and dtypes."""
    key = jax.random.PRNGKey(17)
    h = jax.random.normal(key, (batch, 48, 40)).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (40, 24))
         * 0.1).astype(dtype)
    plan_key = jax.random.PRNGKey(23)

    def loss(ww, backend):
        cfg = WTACRSConfig(budget=0.25, min_rows=4,
                           kernel=KernelConfig(backend=backend))
        return jnp.sum(jnp.sin(wtacrs_linear(h, ww, key=plan_key, cfg=cfg)))

    g_jnp = jax.grad(lambda ww: loss(ww, "jnp"))(w)
    g_ker = jax.grad(lambda ww: loss(ww, "pallas"))(w)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_ker, np.float32),
                               np.asarray(g_jnp, np.float32), **tol)


@pytest.mark.kernel
def test_use_kernel_dh_and_tap_unaffected():
    """The backend routes only the row norms that pick the plan, and
    neither dH nor the gradient-norm tap reads the plan: both are
    bitwise the same under ``pallas`` and ``jnp``."""
    key = jax.random.PRNGKey(29)
    h = jax.random.normal(key, (3, 32, 24))
    w = jax.random.normal(jax.random.fold_in(key, 1), (24, 16)) * 0.1
    znorm = jnp.ones(h.shape[:2])

    def f(hh, zn, backend):
        cfg = WTACRSConfig(budget=0.25, min_rows=4,
                           kernel=KernelConfig(backend=backend))
        return jnp.sum(jnp.sin(wtacrs_linear(
            hh, w, key=jax.random.PRNGKey(31), znorm=zn, cfg=cfg)))

    gh_jnp, gz_jnp = jax.grad(f, argnums=(0, 1))(h, znorm, "jnp")
    gh_ker, gz_ker = jax.grad(f, argnums=(0, 1))(h, znorm, "pallas")
    np.testing.assert_array_equal(np.asarray(gh_jnp), np.asarray(gh_ker))
    np.testing.assert_array_equal(np.asarray(gz_jnp), np.asarray(gz_ker))


def test_estimator_requires_key():
    h = jnp.ones((2, 16, 8))
    w = jnp.ones((8, 4))
    with pytest.raises(ValueError):
        wtacrs_linear(h, w, key=None,
                      cfg=WTACRSConfig(budget=0.25, min_rows=2))
