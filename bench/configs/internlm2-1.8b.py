"""Plain reference for internlm2-1.8b (arXiv:2403.17297), and its weights.

``make_params`` draws the weights from the seed, on the device, in one
jitted call and in the type they are served in.  The layout is the one the
serving program takes (layers stacked on a leading axis; ``wq`` as
``(d, heads, head_dim)``, ``wo`` as ``(heads, head_dim, d)``, ``w1``/``w3``
gate and up, ``w2`` down).  The program and the reference each get their
own copy from the seed; neither sees the other's.

``logits`` is the architecture in plain ``jax.numpy`` and float32 at
``highest`` matmul precision, one layer at a time: RMSNorm, rotary
embedding on the two halves of each head, causal grouped-query attention,
SwiGLU, a final RMSNorm and an untied head.  It takes the bfloat16 weights
as they are served, upcast, and computes everything else in float32.

``fp8=True`` is the control: every matrix multiplied in the layers and the
head is rounded to float8 e4m3 with one scale per output channel, the step
below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def sizes(c: dict) -> dict:
    return {"d": c["hidden_size"], "layers": c["num_hidden_layers"],
            "heads": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "hd": c["head_dim"],
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "eps": float(c["rms_norm_eps"]), "theta": float(c["rope_theta"])}


def param_shapes(c: dict) -> dict:
    s = sizes(c)
    d, L, h, kv, hd, ff, v = (s["d"], s["layers"], s["heads"], s["kv"],
                              s["hd"], s["ff"], s["vocab"])
    return {"embed": (v, d), "head": (d, v), "final_norm": (d,),
            "layers": {"ln1": (L, d), "wq": (L, d, h, hd),
                       "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
                       "wo": (L, h, hd, d), "ln2": (L, d),
                       "w1": (L, d, ff), "w3": (L, d, ff),
                       "w2": (L, ff, d)}}


def _fan_in(name: str, shape: tuple) -> int:
    if name == "embed":
        return 1                        # a lookup table: unit variance rows
    if name == "head":
        return shape[0]
    if name == "wo":
        return shape[1] * shape[2]
    return shape[1]


def make_params(c: dict, seed: int, dtype=jnp.bfloat16):
    """The weights drawn from ``seed`` (32 bits), on the default device."""
    shapes = param_shapes(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def draw(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            if name in ("ln1", "ln2", "final_norm"):
                out.append(jnp.ones(shape, dtype))
                continue
            std = 1.0 / math.sqrt(_fan_in(name, shape))
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            out.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return draw(jax.random.PRNGKey(seed))


# ------------------------------------------------------------- reference
def _fp8(w, axis):
    """``w`` rounded to float8 e4m3, one scale per slice along ``axis``
    (the output channels), back in float32."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _layer(x, p, *, eps, theta, fp8):
    """One decoder layer over a whole sequence ``x`` (S, d), float32."""
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    if fp8:
        for k, axis in (("wq", 0), ("wk", 0), ("wv", 0), ("wo", (0, 1)),
                        ("w1", 0), ("w3", 0), ("w2", 0)):
            f32[k] = _fp8(f32[k], axis)
    s = x.shape[0]
    pos = jnp.arange(s, dtype=jnp.float32)
    h = _rms(x, f32["ln1"], eps)
    q = _rope(jnp.einsum("sd,dnh->snh", h, f32["wq"]), pos, theta)
    k = _rope(jnp.einsum("sd,dnh->snh", h, f32["wk"]), pos, theta)
    v = jnp.einsum("sd,dnh->snh", h, f32["wv"])
    heads, kv, hd = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("snh,tnh->nst", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("nst,tnh->snh", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("snh,nhd->sd", att, f32["wo"])
    h = _rms(x, f32["ln2"], eps)
    return x + (jax.nn.silu(h @ f32["w1"]) * (h @ f32["w3"])) @ f32["w2"]


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, head, *, eps, fp8):
    w = head.astype(jnp.float32)
    if fp8:
        w = _fp8(w, 0)
    return _rms(x, norm.astype(jnp.float32), eps) @ w


def logits(c: dict, params, tokens, first: int, fp8: bool = False):
    """Float32 logits at positions ``first ..`` of one sequence
    ``tokens`` (S,): the reference's forward pass, a layer at a time."""
    s = sizes(c)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(s["layers"]):
            p = {k: v[i] for k, v in params["layers"].items()}
            x = _layer(x, p, eps=s["eps"], theta=s["theta"], fp8=fp8)
        return _head(x[first:], params["final_norm"], params["head"],
                     eps=s["eps"], fp8=fp8)


def served_gaps(c: dict, params, prompt, served, control: bool = False):
    """Per served token, how far the reference's logit of that token lies
    below the reference's best at its position.  With ``control``, also
    the same gap for the token the float8 control puts first there."""
    prompt = np.asarray(prompt)
    served = np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]])
    ref = logits(c, params, seq, prompt.size - 1)
    best = jnp.max(ref, -1)
    gaps = best - jnp.take_along_axis(ref, jnp.asarray(served)[:, None],
                                      -1)[:, 0]
    out = {"gaps": np.asarray(gaps)}
    if control:
        lo = logits(c, params, seq, prompt.size - 1, fp8=True)
        pick = jnp.argmax(lo, -1)
        out["control_gaps"] = np.asarray(
            best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0])
    return out
