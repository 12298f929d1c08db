"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1), without
a query LoRA.

    q            = x W_q                       (H, nope + rope)
    [c, k_pe]    = x W_kv_a;  c = RMSNorm(c)   (rank, rope)
    [k_nope, v]  = c W_kb, c W_vb              (H, nope), (H, v_dim)
    q_pe, k_pe rotated (YaRN, DeepSeek's interleaved pairs); k_pe is one
    head, shared by all
    scores       = (q_nope . k_nope + q_pe . k_pe) * s, causal softmax, . v

with ``s = (nope + rope)^-1/2`` times YaRN's ``mscale_all_dim`` factor
squared.  The cache holds, a position, ``c`` after the norm (``rank``
values) and ``k_pe`` after the rotation (``rope``), the same for every
head: ``{"c": (..., rank), "k_pe": (..., rope)}``.  Two arrays and not one
row of ``rank + rope``: a 576-wide row would be laid out with the
position minor, and writing one position then touches every tile.

The module has ``gqa.py``'s interface (``init_attn``, ``init_cache``,
``CACHE_AXES``, ``write``, ``attend``, ``attend_cached``), so that
``transformer.py`` treats both alike.  Its two forms compute the same
attention two ways:

* ``attend`` — expanded (prefill, training): K and V per head come from
  the latent through ``W_kb``/``W_vb`` and go through
  ``common.attention``; the work grows with the heads' widths, which pays
  at long queries.
* ``attend_cached`` — absorbed (decode): ``q_nope`` is folded into latent
  space through ``W_kb`` (``q_nope . (c W_kb) = (W_kb q_nope) . c``); the
  scores are taken against the cached rows themselves, the weighted
  latent leaves through ``W_vb``; only the cache's rows are read.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from .common import (apply_rope, attention, rms_norm, rope_angles,
                     write_rows, yarn_rope)

__all__ = ["init_attn", "attend", "attend_cached", "softmax_scale",
           "init_cache", "CACHE_AXES", "write"]

CACHE_AXES = {"c": ("layers", "batch", "kv_seq", "kv_latent"),
              "k_pe": ("layers", "batch", "kv_seq", "head_dim")}

write = write_rows


def init_attn(b, cfg) -> None:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    b.add("wq", (d, h, nope + rope), ("embed", "heads", "head_dim"))
    b.add("wkv_a", (d, r + rope), ("embed", "kv_latent"))
    b.add("kv_norm", (r,), ("kv_latent",), init="ones")
    b.add("wkb", (r, h, nope), ("kv_latent", "heads", "head_dim"))
    b.add("wvb", (r, h, vd), ("kv_latent", "heads", "head_dim"))
    b.add("wo", (h, vd, d), ("heads", "head_dim", "embed"))


def init_cache(cfg, n_layers: int, batch: int, max_len: int, dtype):
    """Zeros for the cache of a stack of ``n_layers``: ``c`` (L, B, S,
    rank) and ``k_pe`` (L, B, S, rope)."""
    widths = {"c": cfg.kv_lora_rank, "k_pe": cfg.qk_rope_dim}
    return {k: jnp.zeros((n_layers, batch, max_len, w), dtype)
            for k, w in widths.items()}


def softmax_scale(cfg) -> float:
    s = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.yarn is not None:
        s *= yarn_rope(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)["attn_scale"]
    return s


def _project(cfg, p, h, pos):
    """Queries (B,T,H,nope+rope), rope part rotated, and the cache entries
    ``{"c": (B,T,rank), "k_pe": (B,T,rope)}`` of ``h`` at positions
    ``pos`` (T,)."""
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    q = jnp.einsum("btd,dnh->btnh", h, p["wq"])
    kv = jnp.einsum("btd,dr->btr", h, p["wkv_a"])
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_angles(pos, cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    cos, sin = cos[None], sin[None]
    q_pe = apply_rope(q[..., nope:], cos, sin, interleaved=True)
    k_pe = apply_rope(kv[..., None, r:], cos, sin, interleaved=True)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    return q, {"c": c, "k_pe": k_pe[..., 0, :]}


def attend(cfg, p, h, *, prefix_len=None, q_chunk: int = 0):
    """Causal attention over the sequence ``h`` (B,T,d) with K and V
    expanded per head; positions before ``prefix_len`` attend
    bidirectionally.  Returns (out (B,T,d), its cache entries)."""
    bsz, t, _ = h.shape
    heads, rope = cfg.n_heads, cfg.qk_rope_dim
    q, rows = _project(cfg, p, h, jnp.arange(t))
    c, k_pe = rows["c"], rows["k_pe"]
    k_nope = jnp.einsum("btr,rnh->btnh", c, p["wkb"])
    v = jnp.einsum("btr,rnh->btnh", c, p["wvb"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (bsz, t, heads, rope))],
        -1)
    out = attention(q, k, v, causal=True, prefix_len=prefix_len,
                    q_chunk=q_chunk, scale=softmax_scale(cfg))
    return jnp.einsum("btnh,nhd->btd", out, p["wo"]).astype(h.dtype), rows


def attend_cached(cfg, p, h, cache, cur_len):
    """New tokens ``h`` (B,T,d) at positions ``cur_len ..`` against one
    layer's cache (``c`` (B,S,rank), ``k_pe`` (B,S,rope)), read in place:
    they attend to the cached positions before ``cur_len`` and to
    themselves under one softmax.  Returns (out (B,T,d), the new tokens'
    cache entries for the caller to write)."""
    tq = h.shape[1]
    nope = cfg.qk_nope_dim
    f32, scale = jnp.float32, softmax_scale(cfg)
    q, new = _project(cfg, p, h, cur_len + jnp.arange(tq))
    c, k_pe = cache["c"], cache["k_pe"]
    new = {k: v.astype(cache[k].dtype) for k, v in new.items()}
    q_lat = jnp.einsum("btnh,rnh->btnr", q[..., :nope], p["wkb"],
                       preferred_element_type=f32).astype(c.dtype)
    q_pe = q[..., nope:].astype(k_pe.dtype)
    cached = (jnp.einsum("btnr,bsr->bnts", q_lat, c,
                         preferred_element_type=f32)
              + jnp.einsum("btnh,bsh->bnts", q_pe, k_pe,
                           preferred_element_type=f32)) * scale
    cached = jnp.where(jnp.arange(c.shape[1]) < cur_len, cached, -1e30)
    fresh = (jnp.einsum("btnr,bur->bntu", q_lat, new["c"],
                        preferred_element_type=f32)
             + jnp.einsum("btnh,buh->bntu", q_pe, new["k_pe"],
                          preferred_element_type=f32)) * scale
    fresh = jnp.where(jnp.tri(tq, dtype=bool), fresh, -1e30)
    top = jnp.maximum(cached.max(-1, keepdims=True),
                      fresh.max(-1, keepdims=True))
    cached, fresh = jnp.exp(cached - top), jnp.exp(fresh - top)
    total = cached.sum(-1, keepdims=True) + fresh.sum(-1, keepdims=True)
    lat = (jnp.einsum("bnts,bsr->bntr", (cached / total).astype(c.dtype), c,
                      preferred_element_type=f32)
           + jnp.einsum("bntu,bur->bntr", (fresh / total).astype(c.dtype),
                        new["c"], preferred_element_type=f32))
    lat = lat.swapaxes(1, 2).astype(h.dtype)            # (B,T,H,rank)
    out = jnp.einsum("btnr,rnh->btnh", lat, p["wvb"])
    return jnp.einsum("btnh,nhd->btd", out, p["wo"]).astype(h.dtype), new
