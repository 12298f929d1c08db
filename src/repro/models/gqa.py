"""Grouped-query attention (GQA; MQA and MHA are its edge cases), with
optional per-head RMSNorm of queries and keys (``cfg.qk_norm``).

    q, k, v      = x W_q, x W_k, x W_v        (Hq, hd), (Hkv, hd), (Hkv, hd)
    q, k rotated (RoPE, half-split pairs); query head i reads KV head
    i // (Hq / Hkv)
    scores       = q . k / sqrt(hd), causal softmax, . v

The cache holds, a position, each KV head's rotated ``k`` and its ``v``:
``{"k": (..., Hkv, hd), "v": (..., Hkv, hd)}``.

The interface is ``mla.py``'s, so that ``transformer.py`` treats both
alike: ``init_attn`` adds the weights, ``init_cache``/``CACHE_AXES``/
``write`` own the cache's layout, and two forms compute the attention:

* ``attend`` (prefill, training): the whole sequence, through
  ``common.attention``; returns the sequence's cache entries.
* ``attend_cached`` (decode): new tokens against one layer's cache, read
  in place; returns the new tokens' entries for the caller to ``write``.
"""
from __future__ import annotations

import jax.numpy as jnp

from .common import apply_rope, attention, rms_norm, rope_angles, write_rows

__all__ = ["init_attn", "attend", "attend_cached", "init_cache",
           "CACHE_AXES", "write"]

_KV = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
CACHE_AXES = {"k": _KV, "v": _KV}

write = write_rows


def init_attn(b, cfg) -> None:
    """The attention weights ``wq``, ``wk``, ``wv``, ``wo``, and the
    per-head ``q_norm``/``k_norm`` where ``cfg.qk_norm``."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    b.add("wq", (d, nq, hd), ("embed", "heads", "head_dim"))
    b.add("wk", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
    b.add("wv", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
    b.add("wo", (nq, hd, d), ("heads", "head_dim", "embed"))
    if cfg.qk_norm:
        b.add("q_norm", (hd,), ("head_dim",), init="ones")
        b.add("k_norm", (hd,), ("head_dim",), init="ones")


def init_cache(cfg, n_layers: int, batch: int, max_len: int, dtype):
    """Zeros for the cache of a stack of ``n_layers``: ``k`` and ``v``,
    each (L, B, S, Hkv, hd)."""
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _project(cfg, p, h, pos):
    """Queries (B,T,Hq,hd) and the cache entries ``{"k", "v"}``
    (B,T,Hkv,hd) of ``h`` at positions ``pos`` (T,), q and k rotated."""
    q = jnp.einsum("btd,dnh->btnh", h, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", h, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", h, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta)
    cos, sin = cos[None], sin[None]
    return apply_rope(q, cos, sin), {"k": apply_rope(k, cos, sin), "v": v}


def _out(p, out, h):
    return jnp.einsum("btnh,nhd->btd", out, p["wo"]).astype(h.dtype)


def attend(cfg, p, h, *, prefix_len=None, q_chunk: int = 0):
    """Causal attention over the sequence ``h`` (B,T,d); positions before
    ``prefix_len`` attend bidirectionally (prefix-LM).  Returns (out
    (B,T,d), its cache entries)."""
    q, kv = _project(cfg, p, h, jnp.arange(h.shape[1]))
    out = attention(q, kv["k"], kv["v"], causal=True, prefix_len=prefix_len,
                    q_chunk=q_chunk)
    return _out(p, out, h), kv


def attend_cached(cfg, p, h, cache, cur_len):
    """New tokens ``h`` (B,T,d) at positions ``cur_len ..`` against one
    layer's cache (``k``, ``v`` (B,S,Hkv,hd)), read in place: they attend
    to the cached positions before ``cur_len`` and to themselves under one
    softmax.  Returns (out (B,T,d), the new tokens' cache entries for the
    caller to write)."""
    bsz, tq, _ = h.shape
    hd, hq, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q, new = _project(cfg, p, h, cur_len + jnp.arange(tq))
    ck, cv = cache["k"], cache["v"]
    new = {n: a.astype(cache[n].dtype) for n, a in new.items()}
    qg = q.reshape(bsz, tq, hkv, hq // hkv, hd)
    f32, scale = jnp.float32, jnp.sqrt(jnp.float32(hd))
    cached = jnp.einsum("btkgh,bskh->bkgts", qg, ck,
                        preferred_element_type=f32) / scale
    cached = jnp.where(jnp.arange(ck.shape[1]) < cur_len, cached, -1e30)
    fresh = jnp.einsum("btkgh,bukh->bkgtu", qg, new["k"],
                       preferred_element_type=f32) / scale
    fresh = jnp.where(jnp.tri(tq, dtype=bool), fresh, -1e30)
    top = jnp.maximum(cached.max(-1, keepdims=True),
                      fresh.max(-1, keepdims=True))
    cached, fresh = jnp.exp(cached - top), jnp.exp(fresh - top)
    total = cached.sum(-1, keepdims=True) + fresh.sum(-1, keepdims=True)
    out = (jnp.einsum("bkgts,bskh->btkgh", (cached / total).astype(cv.dtype),
                      cv, preferred_element_type=f32)
           + jnp.einsum("bkgtu,bukh->btkgh", (fresh / total).astype(cv.dtype),
                        new["v"], preferred_element_type=f32))
    return _out(p, out.reshape(bsz, tq, hq, hd).astype(h.dtype), h), new
