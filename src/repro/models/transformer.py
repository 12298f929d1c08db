"""Decoder-only transformer driver for the dense / moe / mla / audio / vlm
families.

One implementation covers:
  * dense GQA (+ optional qk_norm) — qwen3-14b/32b, codeqwen1.5-7b, internlm2;
  * MoE FFN — qwen3-moe-30b/235b (see moe.py);
  * latent attention with an expert FFN after leading dense layers —
    deepseek-v2-lite (mla.py, moe.py); its cache holds the latent and the
    rotary key a position, and its expert layers count the pairs they
    route;
  * multi-codebook audio LM — musicgen (sum-of-codebook embeddings, K heads);
  * prefix-LM VLM — paligemma (stub patch embeddings + projector, MQA,
    logit soft-capping, sqrt(d) embedding scale).

Layers are stacked on a leading "layers" axis and executed with
``jax.lax.scan`` (optionally rematerialized) so trace/compile cost is O(1)
in depth.  Leading dense layers (``cfg.dense_layers``) are a stack of their
own, ``params["dense_layers"]``, scanned before ``params["layers"]``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import (DTYPES, ParamBuilder, apply_rope, attention,
                     cross_entropy, rms_norm, rope_angles, stack_layers,
                     swiglu)
from ..sharding.context import constrain
from . import mla
from .moe import init_moe, load_balance_loss, moe_ffn

__all__ = ["Runtime", "init", "forward", "train_loss", "prefill",
           "decode_step", "init_cache"]


@dataclass(frozen=True)
class Runtime:
    """Execution knobs (perf levers — see EXPERIMENTS.md §Perf)."""

    q_chunk: int = 1024          # query-chunked attention threshold
    remat: str = "none"          # none | full — scan-level rematerialization
    moe_aux_weight: float = 0.01
    moe_impl: str = "gspmd"      # gspmd (sorted dispatch) | ep (shard_map a2a)


COUNTERS = ("moe_pairs", "moe_rows")


# ------------------------------------------------------------------- init
def _init_layer(b: ParamBuilder, cfg, experts: bool) -> None:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    b.add("ln1", (d,), ("embed",), init="ones")
    if cfg.family == "mla":
        mla.init_attn(b, cfg)
    else:
        b.add("wq", (d, nq, hd), ("embed", "heads", "head_dim"))
        b.add("wk", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
        b.add("wv", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
        b.add("wo", (nq, hd, d), ("heads", "head_dim", "embed"))
    if cfg.qk_norm:
        b.add("q_norm", (hd,), ("head_dim",), init="ones")
        b.add("k_norm", (hd,), ("head_dim",), init="ones")
    b.add("ln2", (d,), ("embed",), init="ones")
    if experts:
        init_moe(b.sub("moe"), cfg)
    else:
        b.add("w1", (d, cfg.d_ff), ("embed", "ff"))
        b.add("w3", (d, cfg.d_ff), ("embed", "ff"))
        b.add("w2", (cfg.d_ff, d), ("ff", "embed"))


def init(cfg, key: jax.Array):
    """Returns (params, logical-axis specs)."""
    dtype = DTYPES[cfg.dtype]
    b = ParamBuilder(key, dtype)
    d, v = cfg.d_model, cfg.vocab_size
    if cfg.family == "audio":
        b.add("embed", (cfg.n_codebooks, v, d), ("codebooks", "vocab", "embed"))
        b.add("head", (cfg.n_codebooks, d, v), ("codebooks", "embed", "vocab"))
    else:
        b.add("embed", (v, d), ("vocab", "embed"))
        if not cfg.tie_embeddings:
            b.add("head", (d, v), ("embed", "vocab"))
    if cfg.family == "vlm":
        b.add("vis_proj", (cfg.vision_embed_dim, d), ("vision", "embed"))
    b.add("final_norm", (d,), ("embed",), init="ones")

    def stack(name, n):
        experts = name == "layers" and cfg.moe_experts > 0
        return stack_layers(b._next(name), n,
                            lambda lb: _init_layer(lb, cfg, experts), dtype)

    stacked = {name: stack(name, n) for name, n in _stack_sizes(cfg).items()}
    params, specs = b.build()
    for name, (p, s) in stacked.items():
        params[name], specs[name] = p, s
    return params, specs


def _stack_sizes(cfg) -> dict[str, int]:
    """Layers per stack, in the order they run: the leading dense layers,
    where the config has them, then the rest."""
    lead = {"dense_layers": cfg.dense_layers} if cfg.dense_layers else {}
    return {**lead, "layers": cfg.n_layers - cfg.dense_layers}


def _stacks(cfg, params) -> list[tuple[str, dict]]:
    return [(name, params[name]) for name in _stack_sizes(cfg)]


# ------------------------------------------------------------------ layers
def _attn(cfg, p, x, *, cache_kv=None, cur_len=None, pos_offset=0,
          prefix_len=None, rt: Runtime = Runtime()):
    """One attention sub-block. Returns (out, kv).

    Without ``cache_kv`` (train / prefill) ``kv`` is the sequence's K/V.
    With it (decode) the cache is only read: the new tokens attend to the
    cached positions before ``cur_len`` and to themselves under one
    softmax, and ``kv`` is the new tokens' K/V for the caller to write."""
    bsz, tq, d = x.shape
    hd = cfg.resolved_head_dim
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("btd,dnh->btnh", h, p["wq"])
    k = jnp.einsum("btd,dnh->btnh", h, p["wk"])
    v = jnp.einsum("btd,dnh->btnh", h, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if cache_kv is None:
        pos = pos_offset + jnp.arange(tq)
        cos, sin = rope_angles(pos, hd, cfg.rope_theta)
        q = apply_rope(q, cos[None], sin[None])
        k = apply_rope(k, cos[None], sin[None])
        out = attention(q, k, v, causal=True, prefix_len=prefix_len,
                        q_chunk=rt.q_chunk)
        new_cache = (k, v)
    else:
        ck, cv = cache_kv                      # (B, Smax, Hkv, hd), read only
        pos = cur_len + jnp.arange(tq)         # decode: tq == 1
        cos, sin = rope_angles(pos, hd, cfg.rope_theta)
        q = apply_rope(q, cos[None], sin[None])
        k = apply_rope(k, cos[None], sin[None]).astype(ck.dtype)
        v = v.astype(cv.dtype)
        hq, hkv = cfg.n_heads, cfg.n_kv_heads
        qg = q.reshape(bsz, tq, hkv, hq // hkv, hd)
        f32, scale = jnp.float32, jnp.sqrt(jnp.float32(hd))
        cached = jnp.einsum("btkgh,bskh->bkgts", qg, ck,
                            preferred_element_type=f32) / scale
        cached = jnp.where(jnp.arange(ck.shape[1]) < cur_len, cached, -1e30)
        fresh = jnp.einsum("btkgh,bukh->bkgtu", qg, k,
                           preferred_element_type=f32) / scale
        fresh = jnp.where(jnp.tri(tq, dtype=bool), fresh, -1e30)
        top = jnp.maximum(cached.max(-1, keepdims=True),
                          fresh.max(-1, keepdims=True))
        cached, fresh = jnp.exp(cached - top), jnp.exp(fresh - top)
        total = cached.sum(-1, keepdims=True) + fresh.sum(-1, keepdims=True)
        out = (jnp.einsum("bkgts,bskh->btkgh", (cached / total).astype(cv.dtype),
                          cv, preferred_element_type=f32)
               + jnp.einsum("bkgtu,bukh->btkgh", (fresh / total).astype(v.dtype),
                            v, preferred_element_type=f32))
        out = out.reshape(bsz, tq, hq, hd).astype(x.dtype)
        new_cache = (k, v)
    return jnp.einsum("btnh,nhd->btd", out, p["wo"]).astype(x.dtype), new_cache


def _block(cfg, p, x, *, cache_kv=None, cur_len=None, pos_offset=0,
           prefix_len=None, rt: Runtime = Runtime()):
    """One layer.  Returns (out, the new tokens' cache entries, router
    probs, the expert layer's counters or None)."""
    x = constrain(x, ("batch", "seq", "embed_act"))
    if cfg.family == "mla":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cache_kv is None:
            attn_out, new_cache = mla.expanded(cfg, p, h, pos_offset=pos_offset,
                                               q_chunk=rt.q_chunk)
        else:
            attn_out, new_cache = mla.absorbed(cfg, p, h, cache_kv, cur_len)
    else:
        attn_out, new_cache = _attn(cfg, p, x, cache_kv=cache_kv,
                                    cur_len=cur_len, pos_offset=pos_offset,
                                    prefix_len=prefix_len, rt=rt)
    x = x + attn_out
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    counts = None
    if "moe" in p:
        from ..sharding.context import current_mesh
        mesh = current_mesh()
        if rt.moe_impl == "ep" and mesh is not None:
            from .moe_ep import moe_ffn_ep
            ffn_out, router_probs = moe_ffn_ep(p["moe"], h, cfg, mesh)
        else:
            ffn_out, router_probs, counts = moe_ffn(p["moe"], h, cfg)
    else:
        ffn_out = swiglu(h, p["w1"], p["w3"], p["w2"])
        router_probs = jnp.zeros((1, 1), jnp.float32)
    out = constrain(x + ffn_out, ("batch", "seq", "embed_act"))
    return out, new_cache, router_probs, counts


def _run_layers(cfg, layers, x, *, cache=None, cur_len=None, pos_offset=0,
                prefix_len=None, rt: Runtime = Runtime()):
    """scan over the stacked layer axis.  With ``cache`` each layer reads
    its slice of the stacked cache (GQA: the K/V pair; MLA: the latent
    rows) and the scan emits only the new tokens' entries, e.g.
    (L, B, Tq, Hkv, hd), for ``decode_step`` to write.  Returns (x, those
    entries or None, router probs, per-layer counters or None)."""

    def body(carry, scanned):
        h = carry
        if cache is None:
            p = scanned
            h2, _, probs, counts = _block(cfg, p, h, pos_offset=pos_offset,
                                          prefix_len=prefix_len, rt=rt)
            return h2, (probs, counts)
        p, layer_cache = scanned
        h2, new, probs, counts = _block(cfg, p, h, cache_kv=layer_cache,
                                        cur_len=cur_len, rt=rt)
        return h2, (new, probs, counts)

    if rt.remat == "save_a2a":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "moe_a2a"))
    elif rt.remat != "none":
        body = jax.checkpoint(body)

    if cache is None:
        x, (probs, counts) = jax.lax.scan(body, x, layers)
        return x, None, probs, counts
    x, (new, probs, counts) = jax.lax.scan(body, x, (layers, cache))
    return x, new, probs, counts


def _count(total, counts):
    """``total`` (the cache's counters) plus a stack's per-layer counts."""
    if counts is None:
        return total
    return {k: total[k] + counts[k].sum(dtype=jnp.int32) for k in total}


# ----------------------------------------------------------------- embeds
def _embed_tokens(cfg, params, batch):
    d = cfg.d_model
    if cfg.family == "audio":
        # (B, K, T) codebook ids -> sum over K codebook embeddings.
        toks = batch["tokens"]
        parts = [params["embed"][kb][toks[:, kb]] for kb in range(cfg.n_codebooks)]
        return sum(parts), None
    if cfg.family == "vlm":
        patches = batch["patches"].astype(params["vis_proj"].dtype)
        img = patches @ params["vis_proj"]                     # (B, P, d)
        txt = params["embed"][batch["tokens"]]                 # (B, Tt, d)
        x = jnp.concatenate([img, txt], axis=1)
        if cfg.embed_scale:
            x = x * jnp.sqrt(jnp.float32(d)).astype(x.dtype)
        return x, cfg.n_patches
    x = params["embed"][batch["tokens"]]
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.float32(d)).astype(x.dtype)
    return x, None


def _logits(cfg, params, x):
    if cfg.family == "audio":
        out = jnp.einsum("btd,kdv->btkv", x, params["head"])
    elif cfg.tie_embeddings:
        out = jnp.einsum("btd,vd->btv", x, params["embed"])
    else:
        out = jnp.einsum("btd,dv->btv", x, params["head"])
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        out = jnp.tanh(out.astype(jnp.float32) / c) * c
    if cfg.family == "audio":
        out = constrain(out, ("batch", "seq", "codebooks", "vocab"))
    else:
        out = constrain(out, ("batch", "seq", "vocab"))
    return out


# -------------------------------------------------------------- entry pts
def forward(cfg, params, batch, rt: Runtime = Runtime()):
    """Full-sequence forward -> logits (train/prefill share this path)."""
    x, prefix_len = _embed_tokens(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed_act"))
    for _, layers in _stacks(cfg, params):
        x, _, probs, _ = _run_layers(cfg, layers, x, prefix_len=prefix_len,
                                     rt=rt)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "vlm":
        x = x[:, cfg.n_patches:]            # loss only on text positions
    return _logits(cfg, params, x), probs


def train_loss(cfg, params, batch, rt: Runtime = Runtime()):
    logits, probs = forward(cfg, params, batch, rt)
    if cfg.family == "audio":
        tgt = batch["targets"]              # (B, K, T)
        loss = cross_entropy(logits.transpose(0, 2, 1, 3), tgt)
    else:
        loss = cross_entropy(logits, batch["targets"])
    if cfg.family == "moe":
        aux = load_balance_loss(probs.reshape(-1, probs.shape[-1]), None, cfg)
        loss = loss + rt.moe_aux_weight * aux
    return loss


def _zero_counters(cfg) -> dict:
    """The expert layers' counters a cache carries from prefill through
    every decode step (none without expert layers)."""
    if not cfg.moe_experts:
        return {}
    return {"counters": {k: jnp.zeros((), jnp.int32) for k in COUNTERS}}


def init_cache(cfg, batch_size: int, max_len: int, dtype=None):
    """Zeros in the cache's layout.  GQA: ``k``/``v`` (L, B, S, Hkv, hd);
    MLA: per layer stack, the latent rows (``mla.init_cache``)."""
    dtype = dtype or DTYPES[cfg.dtype]
    hd, nkv, L = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_layers
    if cfg.family == "mla":
        cache = {name: mla.init_cache(cfg, n, batch_size, max_len, dtype)
                 for name, n in _stack_sizes(cfg).items()}
    else:
        cache = {"k": jnp.zeros((L, batch_size, max_len, nkv, hd), dtype),
                 "v": jnp.zeros((L, batch_size, max_len, nkv, hd), dtype)}
    return {**cache, "len": jnp.zeros((), jnp.int32), **_zero_counters(cfg)}


def cache_specs(cfg):
    """Logical axes for the cache pytree (sequence is model-sharded for
    decode — flash-decoding style; DESIGN.md §5)."""
    counters = {"counters": {k: () for k in COUNTERS}} \
        if cfg.moe_experts else {}
    if cfg.family == "mla":
        return {**{n: mla.CACHE_AXES for n in _stack_sizes(cfg)}, "len": (),
                **counters}
    kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": kv, "v": kv, "len": (), **counters}


def prefill(cfg, params, batch, max_len: int, rt: Runtime = Runtime()):
    """Run the prompt, fill a KV cache, return (last-token logits, cache)."""
    x, prefix_len = _embed_tokens(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed_act"))
    seq = x.shape[1]

    def body(carry, scanned):
        h = carry
        p = scanned
        h2, kv, _, counts = _block(cfg, p, h, prefix_len=prefix_len, rt=rt)
        return h2, (kv, counts)

    def padded(a):                       # (L, B, seq, ...) -> max_len
        return jnp.pad(a, ((0, 0), (0, 0), (0, max_len - seq))
                       + ((0, 0),) * (a.ndim - 3))

    body_fn = jax.checkpoint(body) if rt.remat != "none" else body
    cache = {"len": jnp.int32(seq), **_zero_counters(cfg)}
    for name, layers in _stacks(cfg, params):
        x, (kv, counts) = jax.lax.scan(body_fn, x, layers)
        if cfg.family == "mla":
            cache[name] = jax.tree.map(padded, kv)
        else:
            cache["k"], cache["v"] = padded(kv[0]), padded(kv[1])
        if "counters" in cache:
            cache["counters"] = _count(cache["counters"], counts)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "vlm":
        x = x[:, cfg.n_patches:]
    logits = _logits(cfg, params, x[:, -1:])
    return logits[:, 0], cache


def decode_step(cfg, params, batch, cache, rt: Runtime = Runtime()):
    """One-token step against a filled KV cache (serve_step for decode_*).

    The layers read the cache in place; the new token's K/V is written once
    after them, at ``len``.  Jit it with the cache donated and that write
    updates the buffer in place."""
    if cfg.family == "audio":
        toks = batch["tokens"]              # (B, K, 1)
        parts = [params["embed"][kb][toks[:, kb]] for kb in range(cfg.n_codebooks)]
        x = sum(parts)
    else:
        x = params["embed"][batch["tokens"]]   # (B, 1) -> (B, 1, d)
        if cfg.embed_scale:
            x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    cur = cache["len"]
    new_cache, written = {}, {}
    if "counters" in cache:
        new_cache["counters"] = cache["counters"]
    for name, layers in _stacks(cfg, params):
        group = cache[name] if cfg.family == "mla" \
            else (cache["k"], cache["v"])
        x, written[name], _, counts = _run_layers(cfg, layers, x, cache=group,
                                                  cur_len=cur, rt=rt)
        if "counters" in new_cache:
            new_cache["counters"] = _count(new_cache["counters"], counts)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, x)
    for name, new in written.items():
        if cfg.family == "mla":
            new_cache[name] = mla.write(cache[name], new, cur)
        else:
            at = (0, 0, cur, 0, 0)
            new_cache["k"] = jax.lax.dynamic_update_slice(cache["k"], new[0],
                                                          at)
            new_cache["v"] = jax.lax.dynamic_update_slice(cache["v"], new[1],
                                                          at)
    new_cache["len"] = cur + 1
    return logits[:, 0], new_cache
