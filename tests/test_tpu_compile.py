"""Compile-only checks of the Pallas kernels for a TPU v5e: the training
step's row-norm kernel at the shapes the benchmark's cells feed it.

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


# (B·S, d) of every row-norm call in the benchmark's cells, read from
# their compiled steps (1024 tokens a row): xlstm-125m at batch 8, and
# at 16, which compiles too; MiniCPM-2B at 16; the Kanana-2 share at 4.
# The last pair has rows no block divides and a width with no
# 128-divisor.
_ROW_NORM_SHAPES = [(8192, 768), (8192, 1536),
                    (16384, 768), (16384, 1536),
                    (16384, 2304), (16384, 5760),
                    (4096, 512), (4096, 1536), (4096, 2048),
                    (4096, 4096), (4096, 6144),
                    (4000, 1000)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("n,d", _ROW_NORM_SHAPES)
def test_row_norms_compiles_at_cell_widths(one_chip, n, d, dtype):
    x = jax.ShapeDtypeStruct((n, d), dtype, sharding=one_chip)
    text = _compiled_text(lambda a: ops.row_norms(a, kernel=TPU_KERNELS), x)
    assert "tpu_custom_call" in text


def test_row_norms_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(lambda a: ops.row_norms(a, kernel=TPU_KERNELS), x)
    assert "tpu_custom_call" in text
