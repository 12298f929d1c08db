"""Shared model building blocks (pure JAX, no flax).

Parameters are plain pytrees. Every initializer builds the parameter tree
*and* a parallel tree of logical-axis tuples (MaxText-style) in lockstep via
``ParamBuilder``; ``repro.sharding`` later maps logical axes onto mesh axes
with divisibility-aware fallbacks.

Logical axes used across the zoo:
  "embed" (d_model), "heads", "kv_heads", "head_dim", "ff", "vocab",
  "experts", "layers" (scan stack — never sharded), "state", "conv",
  "vision" — plus "batch"/"seq" on activations.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ParamBuilder", "rms_norm", "rope_angles", "apply_rope",
           "yarn_rope", "attention", "swiglu", "cross_entropy",
           "stack_layers", "DTYPES"]

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


class ParamBuilder:
    """Builds a params pytree and its logical-axis spec pytree in lockstep."""

    def __init__(self, key: jax.Array, dtype=jnp.bfloat16):
        self.key = key
        self.dtype = dtype
        self.params: dict[str, Any] = {}
        self.specs: dict[str, Any] = {}

    def _next(self, name: str) -> jax.Array:
        # crc32, not ``hash``: a str's hash is salted per process, and the
        # same key must give the same parameters in every process
        return jax.random.fold_in(self.key, zlib.crc32(name.encode())
                                  % (2**31 - 1))

    def add(self, name: str, shape: tuple[int, ...], axes: tuple[str, ...],
            init: str = "normal", scale: float | None = None) -> None:
        assert len(shape) == len(axes), (name, shape, axes)
        if init == "zeros":
            p = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            p = jnp.ones(shape, self.dtype)
        else:
            fan_in = shape[0] if len(shape) > 1 else shape[-1]
            s = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
            p = (jax.random.normal(self._next(name), shape, jnp.float32) * s
                 ).astype(self.dtype)
        self.params[name] = p
        self.specs[name] = axes

    def sub(self, name: str) -> "ParamBuilder":
        b = ParamBuilder(self._next(name), self.dtype)
        self.params[name] = b.params
        self.specs[name] = b.specs
        return b

    def build(self) -> tuple[dict, dict]:
        return self.params, self.specs


def stack_layers(key: jax.Array, n_layers: int, make_one, dtype=jnp.bfloat16):
    """Initialize a homogeneous layer stack with a leading 'layers' axis.

    The stacked representation keeps the traced HLO O(1) in depth via
    ``jax.lax.scan`` — essential for compiling 94-layer configs in the
    512-device dry-run.
    """
    def init_at(k):
        b = ParamBuilder(k, dtype)
        make_one(b)
        return b.params

    keys = jax.random.split(key, n_layers)
    params = jax.vmap(init_at)(keys)
    b = ParamBuilder(key, dtype)
    make_one(b)
    specs = jax.tree.map(lambda a: ("layers",) + a, b.specs,
                         is_leaf=lambda x: isinstance(x, tuple))
    return params, specs


# ------------------------------------------------------------------ layers
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)).astype(dt)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_rope(dim: int, theta: float, yarn) -> dict:
    """YaRN's rotary frequencies over ``dim`` dimensions, as DeepSeek-V2
    computes them: each of the ``dim // 2`` frequencies blends the
    interpolated ``base^(-2i/dim) / factor`` and the original over a
    linear ramp between the correction dimensions ``low`` and ``high``
    (the rotations ``beta_fast`` and ``beta_slow`` make over the original
    context).  ``cos_scale`` multiplies cos and sin; ``attn_scale``, the
    square of ``mscale_all_dim``'s factor, multiplies the softmax scale."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor

    def corr(rotations):
        return (dim * math.log(yarn.original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / (high - low if high > low else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: original, 0: interpolated
    return {"inv_freq": (inter * (1 - keep) + extra * keep).astype(np.float32),
            "low": low, "high": high,
            "cos_scale": (yarn_mscale(yarn.factor, yarn.mscale)
                          / yarn_mscale(yarn.factor, yarn.mscale_all_dim)),
            "attn_scale": yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2}


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                yarn=None) -> tuple:
    """NeoX-style rotary angles for given absolute positions (any shape);
    with ``yarn`` (``configs.Yarn``) YaRN's frequencies, at every
    position, and its scale on cos and sin."""
    half = head_dim // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        y = yarn_rope(head_dim, theta, yarn)
        freqs = jnp.asarray(y["inv_freq"])
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    if yarn is None:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * y["cos_scale"], jnp.sin(ang) * y["cos_scale"]


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               interleaved: bool = False) -> jax.Array:
    """x: (..., n_heads, head_dim); cos/sin broadcastable (..., half).

    ``interleaved``: DeepSeek's pairing, dimensions (0, 1), (2, 3), ...:
    the dimensions are de-interleaved (evens, then odds) and then rotated
    by halves; the result stays in that order, as DeepSeek-V2's does."""
    if interleaved:
        x = x.reshape(*x.shape[:-1], -1, 2).swapaxes(-1, -2).reshape(x.shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[..., None, :] if cos.ndim == x.ndim - 1 else cos
    sin = sin[..., None, :] if sin.ndim == x.ndim - 1 else sin
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _attend(q, k, v, mask, scale):
    """q (B,Tq,Hkv,G,hd), k (B,Tk,Hkv,hd), v (B,Tk,Hkv,vd), mask
    (B,1,1,Tq,Tk) or None."""
    scores = jnp.einsum("btkgh,bskh->bkgts", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgts,bskh->btkgh", probs, v)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool, q_offset: Any = 0,
              prefix_len: Any = None,
              q_chunk: int = 0, scale: float | None = None) -> jax.Array:
    """GQA attention. q/k (B,Tq|Tk,Hq|Hkv,hd), v (B,Tk,Hkv,vd) ->
    (B,Tq,Hq,vd).  The softmax scale is ``scale``, by default
    ``1/sqrt(hd)``; the values may be narrower than the queries (MLA).

    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``prefix_len``: PaliGemma-style prefix-LM — positions < prefix_len attend
    bidirectionally, the rest causally.
    ``q_chunk``: if >0 and Tq >= 2*q_chunk, scan over query chunks so the
    score matrix never materializes at (Tq, Tk) — the XLA-level analogue of
    flash attention used for 32k prefill shapes.
    """
    b, tq, hq, hd = q.shape
    tk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, tq, hkv, g, hd)
    vd = v.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    def mask_for(q_pos):
        if not causal:
            return None
        k_pos = jnp.arange(tk)[None, :]
        m = q_pos[:, None] >= k_pos
        if prefix_len is not None:
            both_prefix = (q_pos[:, None] < prefix_len) & (k_pos < prefix_len)
            m = m | both_prefix
        return m[None, None, None]           # (1,1,1,Tq,Tk)

    if q_chunk and tq >= 2 * q_chunk and tq % q_chunk == 0:
        n_chunks = tq // q_chunk
        qs = qg.reshape(b, n_chunks, q_chunk, hkv, g, hd).transpose(1, 0, 2, 3, 4, 5)

        def body(carry, args):
            i, qc = args
            q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
            out = _attend(qc, k, v, mask_for(q_pos), scale)
            return carry, out

        _, outs = jax.lax.scan(body, 0, (jnp.arange(n_chunks), qs))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, tq, hq, vd)
        return out

    q_pos = q_offset + jnp.arange(tq)
    out = _attend(qg, k, v, mask_for(q_pos), scale)
    return out.reshape(b, tq, hq, vd)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array, w2: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ w1) * (x @ w3)
    return h @ w2


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  weights: jax.Array | None = None) -> jax.Array:
    """Mean token CE. logits (..., V) any dtype; targets int (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
