"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

All kernel dispatch goes through ``KernelConfig`` (backend pallas =
interpret mode on CPU).  Block sizes are pinned via config overrides so
the sweeps exercise ragged/tiny blocks regardless of the tuning table.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # degrade: property tests skip, example tests run
    from conftest import given, settings, st  # noqa: F401

from repro.core.kernel_config import KernelConfig
from repro.kernels import ops, ref

RNG = np.random.RandomState(0)


def icfg(**blocks):
    """Interpret-mode Pallas config with pinned blocks (no table)."""
    return KernelConfig(backend="pallas", autotune=False, **blocks)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d", [(64, 64), (100, 96), (33, 130),
                                 (256, 512), (8, 8)])
def test_row_norms(n, d, dtype):
    x = jnp.asarray(RNG.randn(n, d), dtype)
    got = ops.row_norms(x, kernel=icfg(block_rows=32, block_d=64))
    want = ref.row_norms_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


def test_row_norms_jnp_backend():
    x = jnp.asarray(RNG.randn(40, 24), jnp.float32)
    got = ops.row_norms(x, kernel=KernelConfig(backend="jnp"))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.row_norms_ref(x)), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,k", [(64, 96, 16), (50, 130, 20), (16, 8, 16),
                                   (128, 256, 40)])
def test_gather_scale(n, d, k, dtype):
    x = jnp.asarray(RNG.randn(n, d), dtype)
    idx = jnp.asarray(RNG.randint(0, n, (k,)), jnp.int32)
    scale = jnp.asarray(RNG.rand(k), jnp.float32)
    got = ops.gather_scale(x, idx, scale, kernel=icfg(block_d=64))
    want = ref.gather_scale_ref(x, idx, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,di,do,n", [(16, 32, 24, 64), (20, 130, 70, 50),
                                       (8, 16, 16, 16), (64, 128, 96, 200)])
def test_sampled_matmul(k, di, do, n, dtype):
    hs = jnp.asarray(RNG.randn(k, di), dtype)
    dz = jnp.asarray(RNG.randn(n, do), dtype)
    idx = jnp.asarray(RNG.randint(0, n, (k,)), jnp.int32)
    scale = jnp.asarray(RNG.rand(k), jnp.float32)
    got = ops.sampled_matmul(hs, dz, idx, scale,
                             kernel=icfg(bm=16, bn=16, bk=8))
    want = ref.sampled_matmul_ref(hs, dz, idx, scale)
    tol = dict(rtol=3e-2, atol=3e-1) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(4, 80), d=st.integers(4, 100), k=st.integers(1, 40),
       seed=st.integers(0, 10_000))
def test_gather_scale_property(n, d, k, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (k,)), jnp.int32)
    scale = jnp.asarray(rng.rand(k), jnp.float32)
    got = ops.gather_scale(x, idx, scale, kernel=icfg(block_d=32))
    want = ref.gather_scale_ref(x, idx, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 32), di=st.integers(4, 64), do=st.integers(4, 48),
       n=st.integers(4, 64), seed=st.integers(0, 10_000))
def test_fused_sampled_dw_property(k, di, do, n, seed):
    rng = np.random.RandomState(seed)
    hs = jnp.asarray(rng.randn(k, di), jnp.float32)
    dz = jnp.asarray(rng.randn(n, do), jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (k,)), jnp.int32)
    scale = jnp.asarray(rng.rand(k), jnp.float32)
    got = ops.fused_sampled_dw(hs, dz, idx, scale,
                               kernel=icfg(bm=16, bn=16, bk=8))
    want = ref.sampled_matmul_ref(hs, dz, idx, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,k,di,do,n", [
    (1, 16, 32, 24, 64),        # degenerate batch, aligned blocks
    (2, 20, 130, 70, 50),       # ragged last block in every dim
    (8, 12, 33, 17, 30),        # larger batch, ragged + tiny dims
])
def test_fused_matches_unfused_composition(b, k, di, do, n, dtype):
    """ACCEPTANCE: the fused kernel bit-matches (within f32-accumulation
    tolerance) the unfused row_norms -> plan -> gather_scale ->
    sampled_matmul composition, across B x dtype x ragged shapes."""
    from repro.core import plans as plans_lib

    cfg = icfg(bm=16, bn=16, bk=8, block_rows=16, block_d=32)
    h = jnp.asarray(RNG.randn(b, n, di), dtype)
    dz = jnp.asarray(RNG.randn(b, n, do), dtype)

    # Unfused pipeline, per sample: kernel row-norms feed the plan, the
    # kernel gather builds H', the legacy padded kernel does the GEMM.
    idxs, scales, hsubs = [], [], []
    for i in range(b):
        norms = ops.row_norms(h[i], kernel=cfg)
        p = norms / jnp.sum(norms)
        plan = plans_lib.wtacrs_plan(p, k, jax.random.PRNGKey(i))
        idxs.append(plan.idx)
        scales.append(plan.scale)
        hsubs.append(ops.gather_scale(h[i], plan.idx,
                                      jnp.ones((k,), jnp.float32),
                                      kernel=cfg))
    idx = jnp.stack(idxs)
    scale = jnp.stack(scales)
    hsub = jnp.stack(hsubs)
    unfused = ops.sampled_matmul(hsub, dz, idx, scale, kernel=cfg)

    fused = ops.fused_sampled_dw(hsub, dz, idx, scale, kernel=cfg)
    oracle = ref.sampled_matmul_batched_ref(hsub, dz, idx, scale)

    tol = dict(rtol=3e-2, atol=3e-1 * b) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4 * b)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               **tol)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(oracle),
                               **tol)


@pytest.mark.kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,k,di,do,n", [
    (1, 16, 32, 24, 64),
    (2, 20, 130, 70, 50),
    (8, 12, 33, 17, 30),
])
def test_sampled_matmul_batched(b, k, di, do, n, dtype):
    """Batched kernels == sum_b of the per-sample oracle, across B,
    dtype and ragged-last-block shapes (interpret mode on CPU)."""
    cfg = icfg(bm=16, bn=16, bk=8)
    hs = jnp.asarray(RNG.randn(b, k, di), dtype)
    dz = jnp.asarray(RNG.randn(b, n, do), dtype)
    idx = jnp.asarray(RNG.randint(0, n, (b, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(b, k), jnp.float32)
    want = ref.sampled_matmul_batched_ref(hs, dz, idx, scale)
    tol = dict(rtol=3e-2, atol=3e-1 * b) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4 * b)
    for fn in (ops.sampled_matmul, ops.fused_sampled_dw):
        got = fn(hs, dz, idx, scale, kernel=cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **tol)


@pytest.mark.kernel
def test_fused_ragged_k_not_dividing_bk():
    """k % bk != 0: the in-kernel tail guard (pl.when + where mask) must
    keep padded slots out of the reduction."""
    b, k, di, do, n = 2, 13, 32, 16, 40
    hs = jnp.asarray(RNG.randn(b, k, di), jnp.float32)
    dz = jnp.asarray(RNG.randn(b, n, do), jnp.float32)
    idx = jnp.asarray(RNG.randint(0, n, (b, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(b, k), jnp.float32)
    got = ops.fused_sampled_dw(hs, dz, idx, scale,
                               kernel=icfg(bm=16, bn=16, bk=4))
    want = ref.sampled_matmul_batched_ref(hs, dz, idx, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-4)


@pytest.mark.kernel
def test_sampled_matmul_batched_matches_stacked_single():
    """The batch-summed kernel equals B independent single-sample kernel
    calls summed — the B == 1 path is exactly the degenerate case."""
    cfg = icfg(bm=16, bn=16, bk=8)
    b, k, di, do, n = 3, 16, 32, 24, 40
    hs = jnp.asarray(RNG.randn(b, k, di), jnp.float32)
    dz = jnp.asarray(RNG.randn(b, n, do), jnp.float32)
    idx = jnp.asarray(RNG.randint(0, n, (b, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(b, k), jnp.float32)
    got = ops.fused_sampled_dw(hs, dz, idx, scale, kernel=cfg)
    want = sum(np.asarray(ops.fused_sampled_dw(hs[i], dz[i], idx[i],
                                               scale[i], kernel=cfg))
               for i in range(b))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_sampled_matmul_matches_linear_backward():
    """Kernel computes exactly the dW the custom_vjp produces."""
    from repro.core import plans as plans_lib

    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(1, 64, 32), jnp.float32)
    dz = jnp.asarray(rng.randn(64, 16), jnp.float32)
    p = jax.random.dirichlet(jax.random.PRNGKey(0), jnp.ones(64))
    plan = plans_lib.wtacrs_plan(p, 20, jax.random.PRNGKey(1))
    h_sub = h[0][plan.idx]
    want = h_sub.T @ (dz[plan.idx] * plan.scale[:, None])
    cfg = icfg(bm=16, bn=16, bk=8)
    for fn in (ops.sampled_matmul, ops.fused_sampled_dw):
        got = fn(h_sub, dz, plan.idx, plan.scale, kernel=cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def _plan_dw(h, dz, key, cfg):
    """The oracle dW of a sampled linear: the plan it builds from (h,
    key, cfg), contracted by ``ref.sampled_matmul_batched_ref``."""
    from repro.core import linear

    k = cfg.budget_rows(h.shape[1])
    idx, scale = linear._make_plans(h, jnp.ones(h.shape[:2], jnp.float32),
                                    jax.random.key_data(key), cfg, k)
    return ref.sampled_matmul_batched_ref(linear._rowgather(h, idx), dz,
                                          idx, scale)


@pytest.mark.kernel
@pytest.mark.parametrize("batch", [1, 2, 8])
def test_shared_backward_routes_through_kernel(batch):
    """Shared-plan dW, under ``backend="pallas"`` and ``"jnp"`` alike,
    equals the oracle contraction on the plan the linear built: the
    backend routes only the plan's row norms, every dW is the same
    gather and dot_general."""
    from repro.core.config import WTACRSConfig
    from repro.core.linear import wtacrs_linear_shared

    rng = np.random.RandomState(11)
    h = jnp.asarray(rng.randn(batch, 64, 32), jnp.float32)
    w1 = jnp.asarray(rng.randn(32, 24) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.randn(32, 16) * 0.1, jnp.float32)
    key = jax.random.PRNGKey(5)

    for backend in ("pallas", "jnp"):
        cfg = WTACRSConfig(budget=0.25, min_rows=4,
                           kernel=KernelConfig(backend=backend))

        def loss(ws):
            a, b = wtacrs_linear_shared(h, ws, key=key, cfg=cfg)
            return jnp.sum(jnp.sin(a)) + jnp.sum(jnp.cos(b))

        g1, g2 = jax.grad(loss)((w1, w2))
        want1 = _plan_dw(h, jnp.cos(h @ w1), key, cfg)
        want2 = _plan_dw(h, -jnp.sin(h @ w2), key, cfg)
        for got, want in ((g1, want1), (g2, want2)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.kernel
def test_per_weight_backward_routes_through_kernel():
    """Per-weight dW, under ``backend="pallas"`` and ``"jnp"`` alike,
    equals the oracle contraction on the plan the linear built."""
    from repro.core.config import WTACRSConfig
    from repro.core.linear import wtacrs_linear

    rng = np.random.RandomState(12)
    h = jnp.asarray(rng.randn(2, 48, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 20) * 0.1, jnp.float32)
    key = jax.random.PRNGKey(9)

    for backend in ("pallas", "jnp"):
        cfg = WTACRSConfig(budget=0.3, min_rows=4,
                           kernel=KernelConfig(backend=backend))
        got = jax.grad(lambda w_: jnp.sum(
            wtacrs_linear(h, w_, key=key, cfg=cfg) ** 2))(w)
        want = _plan_dw(h, 2 * (h @ w), key, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_use_kernel_deprecated_alias():
    """use_kernel=True still routes to Pallas, with a DeprecationWarning
    — and replace() round-trips don't re-fire the warning."""
    import dataclasses

    from repro.core.config import WTACRSConfig

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = WTACRSConfig(use_kernel=True)
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert cfg.kernel.backend == "pallas" and cfg.kernel.use_pallas
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg2 = dataclasses.replace(cfg, budget=0.1)
    assert not w and cfg2.kernel.backend == "pallas"
    # the explicit config API clears the alias
    fresh = cfg.with_kernel(KernelConfig(backend="jnp"))
    assert not fresh.use_kernel and not fresh.kernel.use_pallas


def test_kernel_config_validation():
    with pytest.raises(ValueError):
        KernelConfig(backend="cuda")
    with pytest.raises(ValueError):
        KernelConfig(bm=0)
    with pytest.raises(ValueError):
        KernelConfig(bk=-8)
    cfg = KernelConfig()
    assert cfg.interpret is None and cfg.interpreted   # CPU backend here
    assert not KernelConfig(interpret=False).interpreted
    assert KernelConfig(backend="jnp").use_pallas is False
    assert KernelConfig(backend="pallas").use_pallas is True


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(causal, group, dtype):
    rng = np.random.RandomState(7)
    bh, s, dh = 4, 64, 16
    q = jnp.asarray(rng.randn(bh, s, dh), dtype)
    k = jnp.asarray(rng.randn(bh // group, s, dh), dtype)
    v = jnp.asarray(rng.randn(bh // group, s, dh), dtype)
    got = ops.flash_attention_fwd(q, k, v, group=group, causal=causal,
                                  bq=16, bk=16)
    want = ref.flash_attention_fwd_ref(q, k, v, group=group, causal=causal)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@settings(max_examples=8, deadline=None)
@given(s=st.sampled_from([32, 48, 64]), dh=st.sampled_from([8, 16]),
       seed=st.integers(0, 1000))
def test_flash_attention_kernel_property(s, dh, seed):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(2, s, dh), jnp.float32)
    k = jnp.asarray(rng.randn(2, s, dh), jnp.float32)
    v = jnp.asarray(rng.randn(2, s, dh), jnp.float32)
    got = ops.flash_attention_fwd(q, k, v, group=1, causal=True,
                                  bq=16, bk=16)
    want = ref.flash_attention_fwd_ref(q, k, v, group=1, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


_PER_SHARD_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.kernel_config import KernelConfig
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
cfg = KernelConfig(backend="pallas", autotune=False)
rng = np.random.RandomState(0)
b, k, di, do, n = 4, 12, 32, 24, 40
hs = jnp.asarray(rng.randn(b, k, di), jnp.float32)
dz = jnp.asarray(rng.randn(b, n, do), jnp.float32)
idx = jnp.asarray(rng.randint(0, n, (b, k)), jnp.int32)
scale = jnp.asarray(rng.rand(b, k), jnp.float32)
x = jnp.asarray(rng.randn(b * n, di), jnp.float32)
put = lambda a: jax.device_put(a, NamedSharding(mesh, P("data")))
dw_fn = lambda *a: ops.fused_sampled_dw(*a, kernel=cfg)
norms_fn = lambda a: ops.row_norms(a, kernel=cfg)
with jax.set_mesh(mesh):
    args = tuple(map(put, (hs, dz, idx, scale)))
    assert "shard_map" in str(jax.make_jaxpr(dw_fn)(*args))
    assert "shard_map" in str(jax.make_jaxpr(norms_fn)(put(x)))
    dw = jax.jit(dw_fn)(*args)
    norms = jax.jit(norms_fn)(put(x))
np.testing.assert_allclose(
    np.asarray(dw), np.asarray(ref.sampled_matmul_batched_ref(
        hs, dz, idx, scale)), rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(norms),
                           np.asarray(ref.row_norms_ref(x)), rtol=1e-5)
print("per-shard ok")
"""


def _run_on_four_devices(script):
    """Run ``script`` in a fresh process on four virtual CPU devices;
    return its standard output."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", script],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_kernels_run_per_data_shard_under_mesh():
    """Under a multi-device mesh the kernels run inside shard_map over
    the data axes (the TPU compiler cannot partition a Mosaic kernel):
    the per-shard partial dW sums back to the whole, and row norms keep
    their row split.  Four virtual CPU devices need a fresh process."""
    assert "per-shard ok" in _run_on_four_devices(_PER_SHARD_SCRIPT)


_SHARDED_DW_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.config import WTACRSConfig
from repro.core.kernel_config import KernelConfig
from repro.core.linear import wtacrs_linear, wtacrs_linear_shared
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
rng = np.random.RandomState(0)
h = jnp.asarray(rng.randn(4, 32, 64), jnp.float32)
ws = tuple(jnp.asarray(rng.randn(64, e) * 0.1, jnp.float32)
           for e in (48, 32))
key = jax.random.PRNGKey(3)
for backend in ("jnp", "pallas"):
    cfg = WTACRSConfig(budget=0.25, min_rows=4,
                       kernel=KernelConfig(backend=backend))

    def per_weight(h, ws):
        return sum(jnp.sum(jnp.sin(wtacrs_linear(h, w, key=key, cfg=cfg)))
                   for w in ws)

    def shared(h, ws):
        zs = wtacrs_linear_shared(h, ws, key=key, cfg=cfg)
        return sum(jnp.sum(jnp.sin(z)) for z in zs)

    for loss in (per_weight, shared):
        grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
        want = jax.device_get(grad(h, ws))
        with jax.set_mesh(mesh):
            hs = jax.device_put(h, NamedSharding(mesh, P("data")))
            wss = tuple(jax.device_put(w, NamedSharding(mesh,
                                                        P(None, "model")))
                        for w in ws)
            assert "fused_sampled_dw" not in str(
                jax.make_jaxpr(grad)(hs, wss))
            got = jax.device_get(grad(hs, wss))
        for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                       rtol=1e-4, atol=1e-4)
print("sharded dW ok")
"""


def test_sampled_dw_partitions_under_mesh():
    """The sampled linears' gradients (per-weight and shared-plan, either
    backend) under a data-sharded batch on a (data 2, model 2) mesh
    equal the unsharded ones: the partitioner shards the dW dot_general
    over the data axes and adds the cross-shard sum itself."""
    assert "sharded dW ok" in _run_on_four_devices(_SHARDED_DW_SCRIPT)
