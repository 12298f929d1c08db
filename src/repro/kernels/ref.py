"""Pure-jnp (and pure-python) oracles for every Pallas kernel.

Kept dependency-free of the kernel modules: these are the ground truth the
shape/dtype sweep tests assert against.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["attention_ref", "wkv6_ref", "stream_read_ref", "stream_write_ref",
           "pchase_ref"]


def attention_ref(q, k, v, causal: bool = True):
    """Dense softmax attention with GQA head repetition. Shapes as kernel."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def wkv6_ref(r, k, v, w, u):
    """Sequential WKV6 recurrence (zero init state), f32 outputs.

    y_t = (S_{t-1} + diag(u) k_t v_t^T)^T r_t ;  S_t = diag(w_t) S + k v^T
    """
    b, t, h, kk = r.shape
    vv = v.shape[-1]
    r, k, v, w = (x.astype(jnp.float32) for x in (r, k, v, w))
    u = u.astype(jnp.float32)

    def step(s, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        return wt[..., None] * s + kv, y

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (r, k, v, w))
    state, ys = jax.lax.scan(step, jnp.zeros((b, h, kk, vv), jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1), state


def stream_read_ref(x, block_rows: int):
    """Per-block f32 sums of a (R, C) array cut into (block_rows, C) blocks."""
    return jnp.sum(x.reshape(-1, block_rows * x.shape[1]).astype(jnp.float32),
                   axis=1)


def stream_write_ref(x):
    return x + jnp.asarray(1, x.dtype)


def pchase_ref(perm: np.ndarray, iters: int) -> tuple[int, int]:
    """Python chase oracle: (final cursor, int32-wrapped visit checksum)."""
    cursor, checksum = 0, 0
    p = np.asarray(perm)
    for _ in range(iters):
        cursor = int(p[cursor])
        checksum = (checksum + cursor) & 0xFFFFFFFF
    if checksum >= 2**31:
        checksum -= 2**32
    return cursor, checksum
