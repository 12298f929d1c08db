"""Chunked RWKV6 (Finch) WKV recurrence — Pallas TPU kernel.

Grid = (batch, heads, n_chunks) with the chunk dimension sequential
("arbitrary"): the (K, V) wkv state lives in f32 VMEM scratch across chunk
steps. Within a chunk the per-channel pairwise decay tensor (C, C, K) is
materialized in VMEM — C=32, K<=128 keeps it under 2 MB, comfortably inside
the ~16 MB v5e VMEM together with the r/k/v/w blocks.

This is the TPU-native schedule of ``models.rwkv6.wkv_chunked`` (same math;
cross-checked in tests) and the optimized training path for rwkv6-3b.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv6_chunked_kernel"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_out_ref, s_scr,
            *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)          # (C, K)
    v = v_ref[0, 0].astype(jnp.float32)          # (C, V)
    w = w_ref[0, 0].astype(jnp.float32)          # (C, K)
    u = u_ref[0, 0].astype(jnp.float32)          # (1, K) broadcast row

    lw = jnp.log(w)
    cs = jnp.cumsum(lw, axis=0)                  # L_j inclusive, (C, K)
    d_in = jnp.exp(cs - lw)                      # exp(L_{j-1}), (C, K)
    s = s_scr[...]                               # (K, V)

    # inter-chunk
    y = jax.lax.dot_general(r * d_in, s, (((1,), (0,)), ((), ())))  # (C, V)

    # intra-chunk: att[j, i] = sum_k r_j k_i exp(L_{j-1}[k] - L_i[k]), i < j
    dec = jnp.exp((cs - lw)[:, None, :] - cs[None, :, :])   # (C, C, K)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * dec, axis=-1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(rows > cols, att, 0.0)
    y += jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())))

    # diagonal bonus u
    diag = jnp.sum(r * u * k, axis=-1)           # (C,)
    y += diag[:, None] * v

    # state carry
    total = cs[-1:, :]                           # (1, K)
    kdec = k * jnp.exp(total - cs)               # (C, K)
    s_scr[...] = jnp.exp(total[0])[:, None] * s + \
        jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ())))

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def wkv6_chunked_kernel(r, k, v, w, u, *, chunk: int = 32,
                        interpret=False):
    """r,k,w: (B,T,H,K); v: (B,T,H,V); u: (H,K) -> (y (B,T,H,V) f32,
    state (B,H,K,V) f32). Zero initial state (prefill semantics)."""
    b, t, h, kk = r.shape
    vv = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk

    # (B,T,H,*) -> (B,H,T,*) for chunk-contiguous blocks.
    tr = lambda x: jnp.swapaxes(x, 1, 2)
    rq, kq, vq, wq = tr(r), tr(k), tr(v), tr(w)
    u2 = u[:, None, :]                           # (H, 1, K)

    grid = (b, h, nc)
    blk = lambda d: pl.BlockSpec((1, 1, chunk, d),
                                 lambda b_, h_, c: (b_, h_, c, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            blk(kk), blk(kk), blk(vv), blk(kk),
            pl.BlockSpec((1, 1, kk), lambda b_, h_, c: (h_, 0, 0)),
        ],
        out_specs=[
            blk(vv),
            pl.BlockSpec((1, 1, kk, vv), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, vv), jnp.float32),
            jax.ShapeDtypeStruct((b, h, kk, vv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
        interpret=interpret,
    )(rq, kq, vq, wq, u2)
    return jnp.swapaxes(y, 1, 2), state
