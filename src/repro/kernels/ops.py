"""Jit'd public wrappers around the Pallas kernels.

Every wrapper compiles for the TPU unless the caller names the interpreter:
``interpret=True`` (Pallas' interpreter) or ``pltpu.InterpretParams()`` (the
TPU-semantics interpreter, which also checks DMAs and semaphores).  On a
CPU-only JAX, leaving ``interpret`` out is an error, never a silent switch.
"""
from __future__ import annotations

import jax.numpy as jnp

from .flash_attention import flash_attention
from .pchase_probe import pchase_kernel, pchase_kernel_batch
from .rwkv6_scan import wkv6_chunked_kernel
from .stream_probe import stream_read_kernel, stream_write_kernel

__all__ = ["mha", "wkv6", "stream_read", "stream_write", "pchase",
           "pchase_batch"]


def mha(q, k, v, *, causal=True, block_q=128, block_k=128, interpret=False):
    """Flash attention over (B, S, H, d) activations (model layout)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


def wkv6(r, k, v, w, u, *, chunk=32, interpret=False):
    """Chunked WKV6 over (B, T, H, K) activations; returns (y, state)."""
    return wkv6_chunked_kernel(r, k, v, w, u, chunk=chunk,
                               interpret=interpret)


def stream_read(x, *, block_rows=1024, interpret=False):
    """(R, C) -> per-block f32 sums, streamed HBM -> VMEM."""
    return stream_read_kernel(x, block_rows=block_rows, interpret=interpret)


def stream_write(x, *, block_rows=1024, interpret=False):
    """(R, C) -> x + 1, streamed block by block."""
    return stream_write_kernel(x, block_rows=block_rows, interpret=interpret)


def pchase(perm, *, iters, interpret=False):
    """One HBM-resident chase of ``iters`` dependent loads."""
    return pchase_kernel(perm, iters=iters, interpret=interpret)


def pchase_batch(perms, steps, *, interpret=False):
    """Grid-batched p-chase: (R, N) padded cycles + (R,) per-row chain
    lengths -> (R, 2) [cursor, checksum] rows (one launch per sweep)."""
    return pchase_kernel_batch(perms, jnp.asarray(steps, jnp.int32),
                               interpret=interpret)
