"""Mixture-of-Experts FFN: qwen3-moe (128 experts, top-8, renormalized) and
DeepSeek-V2 (64 experts, top-6 unrenormalized, plus shared experts).

The router scores every expert (float32 softmax) and takes the top-k per
token; the (token, k) pairs whose expert is held here
(``cfg.expert_range``) are computed, every other pair is left out.  Shared
experts, where the config has them, run on every token.  A layer that
holds only some experts computes only their part: on one chip of an
expert-parallel group that is its share, and the shares of all the chips,
with the shared experts counted once, add up to the whole layer.

Two dispatches, chosen by what the trace can see:

* grouped (dropless), where no mesh splits the experts -- one device, the
  serving path: the pairs are sorted by expert, every pair not held sorts
  after the groups, and three ``lax.ragged_dot`` grouped matmuls (SwiGLU)
  compute each held pair once.  No capacity, so prefill and decode
  compute the same pairs.  The grouped matmuls are handed all ``t*k``
  pair rows; above ``TOKEN_CHUNK`` tokens they run a chunk of tokens at a
  time, so the buffers do not grow with a long prefill.
* capacity (GShard), where an activation-sharding context splits the
  "experts" axis over a mesh: each held expert gets a static buffer of
  ``C = t*k/E * moe_capacity_factor`` rows, pairs beyond it are dropped,
  and the buffers carry the "experts" sharding, so GSPMD computes each
  expert where its weights live (it partitions a ``ragged_dot`` over
  sharded expert weights by gathering every pair row to every shard).

Both count ``moe_pairs`` (held pairs routed) and ``moe_rows`` (the rows
handed to the expert matmuls: ``t*k`` per grouped call, padded tokens
included; ``held*C`` with capacity).  ``moe_ep.py`` is the shard_map
expert-parallel form for training meshes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sharding.context import constrain, sharded
from .common import swiglu

__all__ = ["init_moe", "moe_ffn", "load_balance_loss", "TOKEN_CHUNK"]

TOKEN_CHUNK = 8192


def init_moe(b, cfg) -> None:
    d, e = cfg.d_model, cfg.moe_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    held = cfg.expert_range[1]
    b.add("router", (d, e), ("embed", "experts"))
    b.add("w1", (held, d, ff), ("experts", "embed", "ff"))
    b.add("w3", (held, d, ff), ("experts", "embed", "ff"))
    b.add("w2", (held, ff, d), ("experts", "ff", "embed"))
    if cfg.moe_shared_d_ff:
        s, sf = b.sub("shared"), cfg.moe_shared_d_ff
        s.add("w1", (d, sf), ("embed", "ff"))
        s.add("w3", (d, sf), ("embed", "ff"))
        s.add("w2", (sf, d), ("ff", "embed"))


def _grouped(p, xt, local, weight, held: int):
    """The held experts' part for tokens ``xt`` (T, d): ``local`` (T, k)
    the expert of each pair counted from the first held (``held`` where
    not held here), ``weight`` (T, k) its gate weight.  Returns (y (T, d)
    float32, rows handed to the grouped matmuls)."""
    t, k = local.shape
    flat = local.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=held + 1)[:held]
    xs = xt[order // k]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w1"], sizes)) \
        * jax.lax.ragged_dot(xs, p["w3"], sizes)
    ys = jax.lax.ragged_dot(h.astype(xt.dtype), p["w2"], sizes)
    ys = jnp.where((flat[order] < held)[:, None], ys, 0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    y = jnp.einsum("tkd,tk->td", ys[back].reshape(t, k, -1), weight,
                   preferred_element_type=jnp.float32)
    return y, t * k


def _dropless(p, xt, local, weight, held: int):
    t, d = xt.shape
    if t <= TOKEN_CHUNK:
        return _grouped(p, xt, local, weight, held)
    n = -(-t // TOKEN_CHUNK)
    pad = n * TOKEN_CHUNK - t

    def chunks(a, fill):
        a = jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)
        return a.reshape(n, TOKEN_CHUNK, a.shape[-1])

    y, _ = jax.lax.map(
        lambda c: _grouped(p, *c, held),
        (chunks(xt, 0), chunks(local, held), chunks(weight, 0.0)))
    return y.reshape(n * TOKEN_CHUNK, d)[:t], n * TOKEN_CHUNK * local.shape[1]


def _capacity(p, xt, local, weight, held: int, cap: int):
    """As ``_grouped``, through (held, cap, d) expert buffers: the pairs
    past an expert's ``cap`` are dropped."""
    t, k = local.shape
    d = xt.shape[1]
    flat = local.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    # rank within expert = index - group start
    pos = jnp.arange(t * k) - jnp.searchsorted(sorted_e, sorted_e,
                                               side="left")
    keep = (pos < cap) & (sorted_e < held)
    dispatch = jnp.where(keep, sorted_e * cap + pos, held * cap)
    token = order // k
    buf = jnp.zeros((held * cap + 1, d), xt.dtype).at[dispatch].set(xt[token])
    buf = constrain(buf[: held * cap].reshape(held, cap, d),
                    ("experts", "moe_cap", "embed_act"))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    h = constrain(h, ("experts", "moe_cap", "ff"))
    out = jnp.einsum("ecf,efd->ecd", h, p["w2"]).reshape(held * cap, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)], axis=0)
    w = weight.reshape(t * k)[order] * keep
    ys = out[dispatch].astype(jnp.float32) * w[:, None]
    return jnp.zeros((t, d), jnp.float32).at[token].add(ys), held * cap


def moe_ffn(p, x: jax.Array, cfg):
    """x (B,S,d) -> (out (B,S,d), router probs (T,E) for the aux loss,
    counters ``moe_pairs`` (the (token, held expert) pairs routed) and
    ``moe_rows`` (the rows handed to the expert matmuls, padding
    included))."""
    bsz, seq, d = x.shape
    k = cfg.moe_top_k
    first, held = cfg.expert_range
    t = bsz * seq

    xt = x.reshape(t, d)
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    top_w, top_e = jax.lax.top_k(probs, k)                   # (T, k)
    if cfg.moe_norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    local = top_e - first
    mine = (local >= 0) & (local < held)
    local = jnp.where(mine, local, held)
    weight = jnp.where(mine, top_w, 0.0)

    if sharded((held,), ("experts",)):
        cap = max(int(t * k / cfg.moe_experts * cfg.moe_capacity_factor), k)
        y, rows = _capacity(p, xt, local, weight, held, cap)
    else:
        y, rows = _dropless(p, xt, local, weight, held)
    if "shared" in p:
        s = p["shared"]
        y = y + swiglu(xt, s["w1"], s["w3"], s["w2"]).astype(jnp.float32)
    counts = {"moe_pairs": mine.sum(dtype=jnp.int32),
              "moe_rows": jnp.int32(rows)}
    return y.astype(x.dtype).reshape(bsz, seq, d), probs, counts


def load_balance_loss(probs: jax.Array, top_e: jax.Array | None, cfg) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * P_e (f from argmax)."""
    e = cfg.moe_experts
    p_mean = probs.mean(axis=0)                               # (E,)
    hard = jax.nn.one_hot(jnp.argmax(probs, axis=-1), e).mean(axis=0)
    return e * jnp.sum(hard * p_mean)
