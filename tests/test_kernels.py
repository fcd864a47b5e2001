"""Kernels and the sampled dW vs pure-jnp oracles: shape/dtype sweeps +
properties.

The training step runs one Pallas kernel, the plans' row norms
(``KernelConfig`` backend pallas = interpret mode on CPU); the sampled
weight gradient is ``core.linear``'s gather plus dot_general on every
backend.  Small row-norm blocks are pinned by calling the kernel
directly, on rows padded as ``ops.row_norms`` pads them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # degrade: property tests skip, example tests run
    from conftest import given, settings, st  # noqa: F401

from repro.core import linear
from repro.core.config import WTACRSConfig
from repro.core.kernel_config import KernelConfig
from repro.kernels import ops, ref
from repro.kernels import row_norms as norms_kernel

RNG = np.random.RandomState(0)
PALLAS = KernelConfig(backend="pallas")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


def _dw_tol(dtype, b=1):
    """dW against the f32 oracle: bf16 rounds dZ's gathered rows."""
    return dict(rtol=3e-2, atol=3e-1 * b) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4 * b)


def _pinned_row_norms(x, rows, lanes):
    """The row-norm kernel at small blocks, snapped and padded as
    ``ops.row_norms`` snaps and pads its own."""
    n, d = x.shape
    block_rows = ops.sublane_block(n, rows, x.dtype)
    out = norms_kernel.row_norms(ops._pad_rows(x, block_rows),
                                 block_rows=block_rows,
                                 block_d=ops.lane_block(d, lanes),
                                 interpret=True)
    return out[:n]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d", [(64, 64), (100, 96), (33, 130),
                                 (256, 512), (8, 8),
                                 # the cells' widths: xlstm, MiniCPM
                                 (24, 768), (40, 2304), (12, 5760)])
def test_row_norms(n, d, dtype):
    """The kernel at small pinned blocks and ``ops.row_norms`` at its
    own blocks both match the oracle."""
    x = jnp.asarray(RNG.randn(n, d), dtype)
    want = np.asarray(ref.row_norms_ref(x))
    for got in (_pinned_row_norms(x, 32, 64),
                ops.row_norms(x, kernel=PALLAS)):
        np.testing.assert_allclose(np.asarray(got), want, **_tol(dtype))


def test_row_norms_jnp_backend():
    x = jnp.asarray(RNG.randn(40, 24), jnp.float32)
    got = ops.row_norms(x, kernel=KernelConfig(backend="jnp"))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.row_norms_ref(x)), rtol=1e-6)


@pytest.mark.parametrize("dtype,sub", [(jnp.float32, 8), (jnp.bfloat16, 16)])
@pytest.mark.parametrize("n,d,want", [
    (8192, 768, (256, 384)),     # xlstm-125m
    (16384, 2304, (256, 384)),   # MiniCPM-2B attention input
    (16384, 5760, (256, 384)),   # MiniCPM-2B SwiGLU down-projection
    (100, 1000, (100, 1000)),    # no 128-divisor: the full width
])
def test_resolve_blocks_are_tile_legal(n, d, want, dtype, sub):
    """The row-norm kernel's blocks are tile-legal for the TPU: the lane
    block a multiple of 128 dividing d (or the full d), the row block a
    multiple of the sublane count (8 f32, 16 bf16) or all the rows."""
    rows = ops.sublane_block(n, ops.ROW_BLOCK, dtype)
    lanes = ops.lane_block(d, ops.LANE_BLOCK)
    assert (rows, lanes) == want
    assert d % lanes == 0 and (lanes % ops.LANE == 0 or lanes == d)
    assert rows % sub == 0 or rows == n


def test_resolution_clamps_to_divisors():
    """A requested block snaps to the nearest legal one: a lane block to
    a divisor of the dim (the full dim when no multiple of 128 divides
    it), a row block down to whole sublane tiles, never below one."""
    assert ops.lane_block(96, 100) == 96
    assert ops.lane_block(130, 100) == 130
    assert ops.lane_block(768, 100) == 128       # smallest legal above
    assert ops.sublane_block(20, 1000, jnp.float32) == 20
    assert ops.sublane_block(300, 100, jnp.float32) == 96
    assert ops.sublane_block(300, 100, jnp.bfloat16) == 96
    assert ops.sublane_block(300, 4, jnp.bfloat16) == 16


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,k", [(64, 96, 16), (50, 130, 20), (16, 8, 16),
                                   (128, 256, 40)])
def test_gather_scale(n, d, k, dtype):
    """``linear.gather_scaled``, batched, against the per-sample oracle."""
    x = jnp.asarray(RNG.randn(2, n, d), dtype)
    idx = jnp.asarray(RNG.randint(0, n, (2, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(2, k), jnp.float32)
    got = linear.gather_scaled(x, idx, scale)
    want = jax.vmap(ref.gather_scale_ref)(x, idx, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("batched", [False, True])
def test_gather_scaled_rounds_once(batched):
    """The scale is applied in f32 and the product rounded once to dZ's
    dtype: bitwise ``(take(dz).astype(f32) * scale).astype(dz.dtype)``,
    which rounding the scale to bf16 first would not give."""
    b, n, d, k = (3 if batched else 1), 40, 24, 16
    dz = jnp.asarray(RNG.randn(b, n, d), jnp.bfloat16)
    idx = jnp.asarray(RNG.randint(0, n, (b, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(b, k) * 3 + 0.1, jnp.float32)
    got = np.asarray(linear.gather_scaled(dz, idx, scale), np.float32)
    for i in range(b):
        rows = jnp.take(dz[i], idx[i], axis=0)
        want = (rows.astype(jnp.float32)
                * scale[i][:, None]).astype(dz.dtype)
        np.testing.assert_array_equal(got[i], np.asarray(want, np.float32))
        twice = rows * scale[i][:, None].astype(dz.dtype)
        assert not np.array_equal(got[i], np.asarray(twice, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,di,do,n", [(16, 32, 24, 64), (20, 130, 70, 50),
                                       (8, 16, 16, 16), (64, 128, 96, 200)])
def test_sampled_matmul(k, di, do, n, dtype):
    """The step's sampled dW on one sample against the 2-D oracle."""
    hs = jnp.asarray(RNG.randn(k, di), dtype)
    dz = jnp.asarray(RNG.randn(n, do), dtype)
    idx = jnp.asarray(RNG.randint(0, n, (k,)), jnp.int32)
    scale = jnp.asarray(RNG.rand(k), jnp.float32)
    got = linear._sampled_dw(hs[None], dz[None], idx[None], scale[None],
                             jnp.float32)
    want = ref.sampled_matmul_ref(hs, dz, idx, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_dw_tol(dtype))


@settings(max_examples=12, deadline=None)
@given(n=st.integers(4, 80), d=st.integers(4, 100), k=st.integers(1, 40),
       seed=st.integers(0, 10_000))
def test_gather_scale_property(n, d, k, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(1, n, d), jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (1, k)), jnp.int32)
    scale = jnp.asarray(rng.rand(1, k), jnp.float32)
    got = linear.gather_scaled(x, idx, scale)
    want = ref.gather_scale_ref(x[0], idx[0], scale[0])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 32), di=st.integers(4, 64), do=st.integers(4, 48),
       n=st.integers(4, 64), seed=st.integers(0, 10_000))
def test_fused_sampled_dw_property(k, di, do, n, seed):
    """The step's sampled dW (gather, scale, one dot_general) at any
    shape equals the oracle."""
    rng = np.random.RandomState(seed)
    hs = jnp.asarray(rng.randn(k, di), jnp.float32)
    dz = jnp.asarray(rng.randn(n, do), jnp.float32)
    idx = jnp.asarray(rng.randint(0, n, (k,)), jnp.int32)
    scale = jnp.asarray(rng.rand(k), jnp.float32)
    got = linear._sampled_dw(hs[None], dz[None], idx[None], scale[None],
                             jnp.float32)
    want = ref.sampled_matmul_ref(hs, dz, idx, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,k,di,do,n", [
    (1, 16, 32, 24, 64),        # degenerate batch
    (2, 20, 130, 70, 50),       # widths no tile divides
    (8, 12, 33, 17, 30),        # larger batch, tiny dims
])
def test_sampled_matmul_batched(b, k, di, do, n, dtype):
    """The batched sampled dW == sum_b of the per-sample oracle, across
    B, dtype and ragged shapes."""
    hs = jnp.asarray(RNG.randn(b, k, di), dtype)
    dz = jnp.asarray(RNG.randn(b, n, do), dtype)
    idx = jnp.asarray(RNG.randint(0, n, (b, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(b, k), jnp.float32)
    want = ref.sampled_matmul_batched_ref(hs, dz, idx, scale)
    got = linear._sampled_dw(hs, dz, idx, scale, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_dw_tol(dtype, b))


@pytest.mark.kernel
def test_sampled_matmul_batched_matches_stacked_single():
    """The batch-summed dW equals B independent single-sample dWs
    summed — the B == 1 path is exactly the degenerate case."""
    b, k, di, do, n = 3, 16, 32, 24, 40
    hs = jnp.asarray(RNG.randn(b, k, di), jnp.float32)
    dz = jnp.asarray(RNG.randn(b, n, do), jnp.float32)
    idx = jnp.asarray(RNG.randint(0, n, (b, k)), jnp.int32)
    scale = jnp.asarray(RNG.rand(b, k), jnp.float32)
    got = linear._sampled_dw(hs, dz, idx, scale, jnp.float32)
    want = sum(np.asarray(linear._sampled_dw(
        hs[i:i + 1], dz[i:i + 1], idx[i:i + 1], scale[i:i + 1],
        jnp.float32)) for i in range(b))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_sampled_matmul_matches_linear_backward():
    """The sampled dW is exactly H'^T (dZ[idx] * scale) on a WTA-CRS
    plan."""
    from repro.core import plans as plans_lib

    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(1, 64, 32), jnp.float32)
    dz = jnp.asarray(rng.randn(64, 16), jnp.float32)
    p = jax.random.dirichlet(jax.random.PRNGKey(0), jnp.ones(64))
    plan = plans_lib.wtacrs_plan(p, 20, jax.random.PRNGKey(1))
    h_sub = h[0][plan.idx]
    want = h_sub.T @ (dz[plan.idx] * plan.scale[:, None])
    got = linear._sampled_dw(h_sub[None], dz[None], plan.idx[None],
                             plan.scale[None], jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _plan_dw(h, dz, key, cfg):
    """The oracle dW of a sampled linear: the plan it builds from (h,
    key, cfg), contracted by ``ref.sampled_matmul_batched_ref``."""
    k = cfg.budget_rows(h.shape[1])
    idx, scale = linear._make_plans(h, jnp.ones(h.shape[:2], jnp.float32),
                                    jax.random.key_data(key), cfg, k)
    return ref.sampled_matmul_batched_ref(linear._rowgather(h, idx), dz,
                                          idx, scale)


def _grad_tol(want, dtype):
    """bf16 rounds dZ's gathered rows and the dW itself: a few units in
    the last place of the largest entry."""
    if dtype == jnp.bfloat16:
        return dict(rtol=2e-2, atol=2e-2 * float(np.max(np.abs(want))))
    return dict(rtol=1e-4, atol=1e-4)


@pytest.mark.kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [1, 2, 8])
def test_shared_backward_routes_through_kernel(batch, dtype):
    """Shared-plan dW, under ``backend="pallas"`` and ``"jnp"`` alike,
    equals the oracle contraction on the plan the linear built: the
    backend routes only the plan's row norms, every dW is the same
    gather and dot_general."""
    rng = np.random.RandomState(11)
    h = jnp.asarray(rng.randn(batch, 64, 32), dtype)
    w1 = jnp.asarray(rng.randn(32, 24) * 0.1, dtype)
    w2 = jnp.asarray(rng.randn(32, 16) * 0.1, dtype)
    key = jax.random.PRNGKey(5)

    for backend in ("pallas", "jnp"):
        cfg = WTACRSConfig(budget=0.25, min_rows=4,
                           kernel=KernelConfig(backend=backend))

        def loss(ws):
            a, b = linear.wtacrs_linear_shared(h, ws, key=key, cfg=cfg)
            return jnp.sum(jnp.sin(a)) + jnp.sum(jnp.cos(b))

        g1, g2 = jax.grad(loss)((w1, w2))
        want1 = _plan_dw(h, jnp.cos(h @ w1), key, cfg)
        want2 = _plan_dw(h, -jnp.sin(h @ w2), key, cfg)
        for got, want in ((g1, want1), (g2, want2)):
            assert got.dtype == dtype
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want),
                                       **_grad_tol(want, dtype))


@pytest.mark.kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("batch", [1, 2, 8])
def test_per_weight_backward_routes_through_kernel(batch, dtype):
    """Per-weight dW, under ``backend="pallas"`` and ``"jnp"`` alike,
    equals the oracle contraction on the plan the linear built."""
    rng = np.random.RandomState(12)
    h = jnp.asarray(rng.randn(batch, 48, 24), dtype)
    w = jnp.asarray(rng.randn(24, 20) * 0.1, dtype)
    key = jax.random.PRNGKey(9)

    for backend in ("pallas", "jnp"):
        cfg = WTACRSConfig(budget=0.3, min_rows=4,
                           kernel=KernelConfig(backend=backend))
        got = jax.grad(lambda w_: jnp.sum(
            linear.wtacrs_linear(h, w_, key=key, cfg=cfg) ** 2))(w)
        want = _plan_dw(h, 2 * (h @ w), key, cfg)
        assert got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want),
                                   **_grad_tol(want, dtype))


def test_kernel_config_validation():
    assert [f.name for f in dataclasses.fields(KernelConfig)] == [
        "backend", "interpret"]
    with pytest.raises(ValueError):
        KernelConfig(backend="cuda")
    cfg = KernelConfig()
    assert cfg.interpret is None and cfg.interpreted   # CPU backend here
    assert not cfg.use_pallas                          # auto: jnp on CPU
    assert not KernelConfig(interpret=False).interpreted
    assert KernelConfig(interpret=False).use_pallas    # auto: natively
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
cfg = KernelConfig(backend="pallas")
x = jnp.asarray(np.random.RandomState(0).randn(160, 32), jnp.float32)
put = lambda a: jax.device_put(a, NamedSharding(mesh, P("data")))
norms_fn = lambda a: ops.row_norms(a, kernel=cfg)
with jax.set_mesh(mesh):
    assert "shard_map" in str(jax.make_jaxpr(norms_fn)(put(x)))
    norms = jax.jit(norms_fn)(put(x))
    assert norms.sharding.spec == P("data")
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
    """Under a multi-device mesh the row-norm kernel runs inside
    shard_map over the data axes (the TPU compiler cannot partition a
    Mosaic kernel) and its output keeps the row split.  Four virtual CPU
    devices need a fresh process."""
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
