"""Compile-only checks of the Pallas kernels for a TPU v5e: the training
step's row-norm kernel and the standalone sampled-dW op.

The TPU compiler is installed with JAX and compiles for a described
(not attached) ``v5e:2x2`` topology, so these tests catch what
interpret mode cannot — tile-misaligned blocks and slices, illegal
memory-space loads, VMEM overflow — without a chip.  Nothing runs.

The topology is described only inside fixtures: one process at a time
may load the TPU library, so describing it at import would break every
other test worker.  All such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.kernel_config import KernelConfig
from repro.kernels import ops

# What a kernel looks like on a TPU: ``use_pallas`` with native
# compilation (the CPU backend here would resolve ``interpret=True``).
TPU_KERNELS = KernelConfig(backend="pallas", interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


# (B, k, d_in, d_out) at published widths, S = 512 tokens per sample;
# k = 154 is budget 0.3 of 512 (ragged: no block divides it), k = 77 is
# budget 0.15.
_XLSTM = [(4, 154, 768, 1536), (4, 154, 1536, 768)]
_QWEN = [(8, k, d_in, d_out) for k in (154, 77)
         for d_in, d_out in ((2048, 11008), (11008, 2048), (2048, 256))]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("b,k,d_in,d_out", _XLSTM + _QWEN)
def test_fused_sampled_dw_compiles(one_chip, b, k, d_in, d_out, dtype):
    n = 512
    hsub = jax.ShapeDtypeStruct((b, k, d_in), dtype, sharding=one_chip)
    dz = jax.ShapeDtypeStruct((b, n, d_out), dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((b, k), jnp.int32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((b, k), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda h, d, i, s: ops.fused_sampled_dw(h, d, i, s,
                                                kernel=TPU_KERNELS),
        hsub, dz, idx, scale)
    assert "tpu_custom_call" in text


def test_row_norms_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(lambda a: ops.row_norms(a, kernel=TPU_KERNELS), x)
    assert "tpu_custom_call" in text
