"""The probe kernels compile for a TPU v5e at the sizes the chip runs.

Each test lowers and compiles one kernel ahead of time for a described,
unattached ``v5e:2x2`` topology: what the chip's compiler refuses (a block
not aligned to the tiling, a scalar store to VMEM, more VMEM than a kernel
may use) fails here, at no chip time.  Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this module.
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.probes.tpu_runner import TpuRunner
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pchase_probe import (eviction_kernel_batch, pchase_kernel,
                                        pchase_kernel_batch)
from repro.kernels.stream_probe import stream_read_kernel, stream_write_kernel

I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _stream_rows() -> int:
    return (1 << 30) // (4 * TpuRunner.STREAM_COLS)


# name -> (function to jit, [(shape, dtype) of each argument])
KERNELS = {
    # 256 MiB HBM chase buffer, one row
    "pchase_kernel": (functools.partial(pchase_kernel, iters=4096),
                      [((1 << 26,), I32)]),
    # the DeviceMemory latency request: 16 MiB at a 4 KiB stride
    "pchase_kernel_batch": (pchase_kernel_batch,
                            [((1, 1 << 22), I32), ((1,), I32)]),
    "eviction_kernel_batch": (eviction_kernel_batch,
                              [((64, 1 << 16), I32), ((64, 1 << 16), I32),
                               ((64,), I32), ((64,), I32)]),
    # 1 GiB streams, as TpuRunner.bandwidth runs them
    "stream_read_kernel": (
        functools.partial(stream_read_kernel,
                          block_rows=TpuRunner.STREAM_BLOCK_ROWS),
        [((_stream_rows(), TpuRunner.STREAM_COLS), jnp.float32)]),
    "stream_write_kernel": (
        functools.partial(stream_write_kernel,
                          block_rows=TpuRunner.STREAM_BLOCK_ROWS),
        [((_stream_rows(), TpuRunner.STREAM_COLS), jnp.float32)]),
    # internlm2-1.8b heads: 16 query / 8 kv heads of width 128, bf16
    "flash_attention": (flash_attention,
                        [((1, 16, 2048, 128), jnp.bfloat16),
                         ((1, 8, 2048, 128), jnp.bfloat16),
                         ((1, 8, 2048, 128), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    want = sum(jnp.dtype(dt).itemsize * math.prod(shape)
               for shape, dt in specs)
    assert mem.argument_size_in_bytes >= want
    # the kernels stream from HBM in place: no whole-buffer copy
    assert mem.temp_size_in_bytes < want
