"""Plain reference for DeepSeek-V2-Lite (arXiv:2405.04434), one chip's share
of its experts, and its weights.

``make_params`` draws the weights from the seed, on the device, in one
jitted call and in the type they are served in.  The layout is the one the
serving program takes: the leading dense layer stacked on its own
(``dense_layers``), the expert layers after it (``layers``); ``wq`` as
``(d, heads, nope + rope)``, ``wkv_a`` as ``(d, rank + rope)``, ``wkb`` and
``wvb`` (the two halves of ``kv_b_proj``) as ``(rank, heads, width)``,
``wo`` as ``(heads, v_dim, d)``; an expert layer's ``moe`` holds the router
``(d, routed experts)``, the held experts' ``w1``/``w3`` gate and up and
``w2`` down with a leading expert axis, and ``shared`` (the shared experts
as one SwiGLU).  The program and the reference each get their own copy
from the seed; neither sees the other's.

``logits`` is the architecture in plain ``jax.numpy`` and float32 at
``highest`` matmul precision, one layer at a time, in the published form:
latent attention expanded per head (``k_nope``/``v`` from the normalized
latent, the rotary part de-interleaved and rotated with YaRN's frequencies
at every position, one rotary key shared by all heads, the softmax scale
raised by ``mscale_all_dim``); then a dense SwiGLU in the leading layers,
and in the others a float32 softmax router over all the routed experts,
greedy top-k weights left unnormalized, the part of the experts held here
(``deployment.held``) and the shared experts; a final RMSNorm and an untied
head.  It takes the bfloat16 weights as they are served, upcast, and
computes everything else in float32.

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
    dep = c["deployment"]
    return {"d": c["hidden_size"], "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"],
            "heads": c["num_attention_heads"], "rank": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "vd": c["v_head_dim"], "ff": c["intermediate_size"],
            "ef": c["moe_intermediate_size"],
            "sf": c["n_shared_experts"] * c["moe_intermediate_size"],
            "held": c["n_routed_experts"], "first": dep["held"][0],
            "experts": dep["routed_experts"], "topk": c["num_experts_per_tok"],
            "vocab": c["vocab_size"], "eps": float(c["rms_norm_eps"])}


def param_shapes(c: dict) -> dict:
    s = sizes(c)
    d, h, r, nope, rope, vd = (s["d"], s["heads"], s["rank"], s["nope"],
                               s["rope"], s["vd"])
    attn = {"ln1": (d,), "wq": (d, h, nope + rope), "wkv_a": (d, r + rope),
            "kv_norm": (r,), "wkb": (r, h, nope), "wvb": (r, h, vd),
            "wo": (h, vd, d), "ln2": (d,)}
    ff, ef, sf, n = s["ff"], s["ef"], s["sf"], s["held"]
    dense = {**attn, "w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}
    expert = {**attn, "moe": {
        "router": (d, s["experts"]), "w1": (n, d, ef), "w3": (n, d, ef),
        "w2": (n, ef, d),
        "shared": {"w1": (d, sf), "w3": (d, sf), "w2": (sf, d)}}}

    def stacked(tree, depth):
        return jax.tree.map(lambda shape: (depth,) + shape, tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    return {"embed": (s["vocab"], d), "head": (d, s["vocab"]),
            "final_norm": (d,),
            "dense_layers": stacked(dense, s["dense"]),
            "layers": stacked(expert, s["layers"] - s["dense"])}


NORMS = ("ln1", "ln2", "kv_norm", "final_norm")


def _fan_in(name: str, shape: tuple) -> int:
    """Inputs summed by each output of a matrix (stacked on a leading
    layer axis; an expert matrix on a leading expert axis too)."""
    if name == "embed":
        return 1                        # a lookup table: unit variance rows
    if name == "head":
        return shape[0]
    if name in ("wq", "wkb", "wvb"):
        return shape[-3]
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2]


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
            if name in NORMS:
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


# the axes each matrix sums over, for the float8 control's scales
CONTRACTED = {"wq": 0, "wkv_a": 0, "wkb": 0, "wvb": 0, "wo": (0, 1),
              "w1": -2, "w3": -2, "w2": -2, "router": 0}


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(c: dict) -> dict:
    """YaRN as DeepSeek-V2 publishes it (``DeepseekV2YarnRotaryEmbedding``):
    the rotary inverse frequencies, the correction range, the factor on cos
    and sin, and the softmax scale."""
    y, dim, base = c["rope_scaling"], c["qk_rope_head_dim"], c["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]

    def correction_dim(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / (high - low if high != low else 0.001), 0, 1)
    mask = 1.0 - ramp
    m_all = _yarn_mscale(y["factor"], y["mscale_all_dim"])
    return {"inv_freq": inter * (1 - mask) + extra * mask, "low": low,
            "high": high,
            "mscale": _yarn_mscale(y["factor"], y["mscale"]) / m_all,
            "softmax_scale": (c["qk_nope_head_dim"] + dim) ** -0.5
            * m_all * m_all}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotate(x, cos, sin):
    """DeepSeek's pairing: ``view(..., d/2, 2).transpose`` then
    ``x * cos + rotate_half(x) * sin`` with cos/sin over ``d`` dims."""
    x = x.reshape(*x.shape[:-1], -1, 2).swapaxes(-1, -2).reshape(x.shape)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _attention(x, p, cos, sin, s):
    """``x + Attn(RMSNorm(x))`` over a whole sequence ``x`` (S, d)."""
    r, nope = s["rank"], s["nope"]
    h = _rms(x, p["ln1"], s["eps"])
    q = jnp.einsum("sd,dnh->snh", h, p["wq"])
    kv = h @ p["wkv_a"]
    c = _rms(kv[:, :r], p["kv_norm"], s["eps"])
    q_pe = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    k_pe = _rotate(kv[:, r:], cos, sin)
    k_nope = jnp.einsum("sr,rnh->snh", c, p["wkb"])
    v = jnp.einsum("sr,rnh->snh", c, p["wvb"])
    scores = (jnp.einsum("snh,tnh->nst", q[..., :nope], k_nope)
              + jnp.einsum("snh,th->nst", q_pe, k_pe)) * s["softmax_scale"]
    n = x.shape[0]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("nst,tnh->snh", jax.nn.softmax(scores, -1), v)
    return x + jnp.einsum("snh,nhd->sd", att, p["wo"])


def experts(h, p, s):
    """The expert layer on ``h`` (S, d): the router's softmax over all the
    routed experts, greedy top-k, weights unnormalized; the experts held
    here (``first ..`` of ``held``) weighted, plus the shared experts."""
    probs = jax.nn.softmax(h @ p["router"], -1)
    top_w, top_e = jax.lax.top_k(probs, s["topk"])
    y = _swiglu(h, p["shared"]["w1"], p["shared"]["w3"], p["shared"]["w2"])
    for j in range(p["w1"].shape[0]):
        w = jnp.sum(jnp.where(top_e == s["first"] + j, top_w, 0.0), -1)
        y = y + w[:, None] * _swiglu(h, p["w1"][j], p["w3"][j], p["w2"][j])
    return y


def _f32(p, fp8):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    if fp8:
        p = jax.tree_util.tree_map_with_path(
            lambda path, a: _fp8(a, CONTRACTED[path[-1].key])
            if path[-1].key in CONTRACTED else a, p)
    return p


@functools.partial(jax.jit, static_argnames=("s", "fp8"))
def _layer(x, p, cos, sin, *, s, fp8):
    """One decoder layer over a whole sequence ``x`` (S, d), float32."""
    s = dict(s)
    p = _f32(p, fp8)
    x = _attention(x, p, cos, sin, s)
    h = _rms(x, p["ln2"], s["eps"])
    if "moe" in p:
        return x + experts(h, p["moe"], s)
    return x + _swiglu(h, p["w1"], p["w3"], p["w2"])


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
    y = yarn(c)
    s["softmax_scale"] = y["softmax_scale"]
    frozen = tuple(sorted(s.items()))
    pos = np.arange(len(tokens), dtype=np.float64)
    ang = pos[:, None] * y["inv_freq"][None, :]
    ang = np.concatenate([ang, ang], -1)
    cos = jnp.asarray(np.cos(ang) * y["mscale"], jnp.float32)
    sin = jnp.asarray(np.sin(ang) * y["mscale"], jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        for name in ("dense_layers", "layers"):
            stack = params[name]
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                p = jax.tree.map(lambda a, i=i: a[i], stack)
                x = _layer(x, p, cos, sin, s=frozen, fp8=fp8)
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
