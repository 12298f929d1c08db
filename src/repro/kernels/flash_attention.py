"""Blockwise (flash) causal GQA attention — Pallas TPU kernel.

TPU-native adaptation (DESIGN.md): online-softmax accumulation in VMEM f32
scratch, MXU-aligned block shapes (multiples of 128 on the contracting dims),
grid = (batch, q_heads, q_blocks, kv_blocks) with the kv dimension marked
"arbitrary" (sequential) so the running (m, l, acc) carry lives across kv
steps. GQA is expressed in the k/v BlockSpec index maps (q head h reads kv
head h // group). Causality skips fully-masked kv blocks via pl.when.

Compiled for the TPU by default; the CPU tests pass ``interpret=True`` and
check it against ref.py's dense oracle across shape/dtype sweeps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, block_q: int, block_k: int, kv_len: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s *= 1.0 / math.sqrt(q.shape[-1])

        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret=False) -> jax.Array:
    """q (B, Hq, Sq, d); k/v (B, Hkv, Sk, d) -> (B, Hq, Sq, d).

    Sq % block_q == 0 and Sk % block_k == 0 are required (production path
    pads the ragged tail); Hq % Hkv == 0 (GQA).
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0 and sq % block_q == 0 and sk % block_k == 0
    g = hq // hkv
    grid = (b, hq, sq // block_q, sk // block_k)

    kernel = functools.partial(_kernel, causal=causal, block_q=block_q,
                               block_k=block_k, kv_len=sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, iq, ik, g=g: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),    # accumulator
        ],
        interpret=interpret,
    )(q, k, v)
