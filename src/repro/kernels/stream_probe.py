"""Stream bandwidth probe — Pallas TPU kernel (paper §IV-I, TPU-native).

MT4G's bandwidth benchmark issues wide vector loads from many threads; the
TPU-native equivalent streams HBM->VMEM tiles across a grid, which Pallas
double-buffers so the DMA engines stay busy (DESIGN.md adaptation note 4).
Two modes over a 2-D ``(rows, 128·k)`` array with ``(block_rows, 128·k)``
blocks:

  * read  — one f32 sum per block, written to SMEM (bytes in, ~0 out);
  * write — block copy ``x + 1`` (bytes in == bytes out).

The caller times a call around ``block_until_ready`` and divides the bytes
moved by it.  On the CPU the same kernels run in the interpreter, which
checks their results and nothing about speed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["stream_read_kernel", "stream_write_kernel"]


def _read_kernel(x_ref, out_ref):
    out_ref[pl.program_id(0)] = jnp.sum(x_ref[...].astype(jnp.float32))


def _write_kernel(x_ref, y_ref):
    y_ref[...] = x_ref[...] + jnp.asarray(1, x_ref.dtype)


def _grid(x: jax.Array, block_rows: int) -> tuple[int, pl.BlockSpec, int]:
    rows, cols = x.shape
    assert rows % block_rows == 0 and cols % 128 == 0, (x.shape, block_rows)
    block = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return rows // block_rows, block, block_rows * cols * x.dtype.itemsize


def _params(block_bytes: int) -> pltpu.CompilerParams:
    # Two buffers per side (Pallas double-buffers), in and out, plus slack.
    return pltpu.CompilerParams(vmem_limit_bytes=4 * block_bytes + (8 << 20))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_read_kernel(x: jax.Array, *, block_rows: int = 1024,
                       interpret=False) -> jax.Array:
    """x (R, C) -> per-block f32 sums (R // block_rows,); C % 128 == 0."""
    g, block, block_bytes = _grid(x, block_rows)
    return pl.pallas_call(
        _read_kernel,
        grid=(g,),
        in_specs=[block],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((g,), jnp.float32),
        compiler_params=_params(block_bytes),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def stream_write_kernel(x: jax.Array, *, block_rows: int = 1024,
                        interpret=False) -> jax.Array:
    """x (R, C) -> x + 1, streamed block by block (read+write bytes)."""
    g, block, block_bytes = _grid(x, block_rows)
    return pl.pallas_call(
        _write_kernel,
        grid=(g,),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(block_bytes),
        interpret=interpret,
    )(x)
