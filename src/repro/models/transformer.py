"""Decoder-only transformer driver for the dense / moe / audio / vlm families.

One implementation covers:
  * dense GQA (+ optional qk_norm) — qwen3-14b/32b, codeqwen1.5-7b, internlm2;
  * MoE FFN — qwen3-moe-30b/235b (see moe.py);
  * multi-codebook audio LM — musicgen (sum-of-codebook embeddings, K heads);
  * prefix-LM VLM — paligemma (stub patch embeddings + projector, MQA,
    logit soft-capping, sqrt(d) embedding scale).

Layers are stacked on a leading "layers" axis and executed with
``jax.lax.scan`` (optionally rematerialized) so trace/compile cost is O(1)
in depth.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import (DTYPES, ParamBuilder, apply_rope, attention,
                     cross_entropy, rms_norm, rope_angles, stack_layers,
                     swiglu)
from ..sharding.context import constrain
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


# ------------------------------------------------------------------- init
def _init_layer(b: ParamBuilder, cfg) -> None:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    b.add("ln1", (d,), ("embed",), init="ones")
    b.add("wq", (d, nq, hd), ("embed", "heads", "head_dim"))
    b.add("wk", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
    b.add("wv", (d, nkv, hd), ("embed", "kv_heads", "head_dim"))
    b.add("wo", (nq, hd, d), ("heads", "head_dim", "embed"))
    if cfg.qk_norm:
        b.add("q_norm", (hd,), ("head_dim",), init="ones")
        b.add("k_norm", (hd,), ("head_dim",), init="ones")
    b.add("ln2", (d,), ("embed",), init="ones")
    if cfg.family == "moe":
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

    layer_params, layer_specs = stack_layers(
        b._next("layers"), cfg.n_layers, lambda lb: _init_layer(lb, cfg), dtype)
    params, specs = b.build()
    params["layers"], specs["layers"] = layer_params, layer_specs
    return params, specs


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
    x = constrain(x, ("batch", "seq", "embed_act"))
    attn_out, new_cache = _attn(cfg, p, x, cache_kv=cache_kv, cur_len=cur_len,
                                pos_offset=pos_offset, prefix_len=prefix_len,
                                rt=rt)
    x = x + attn_out
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        from ..sharding.context import current_mesh
        mesh = current_mesh()
        if rt.moe_impl == "ep" and mesh is not None:
            from .moe_ep import moe_ffn_ep
            ffn_out, router_probs = moe_ffn_ep(p["moe"], h, cfg, mesh)
        else:
            ffn_out, router_probs = moe_ffn(p["moe"], h, cfg)
    else:
        ffn_out = swiglu(h, p["w1"], p["w3"], p["w2"])
        router_probs = jnp.zeros((1, 1), jnp.float32)
    out = constrain(x + ffn_out, ("batch", "seq", "embed_act"))
    return out, new_cache, router_probs


def _run_layers(cfg, layers, x, *, cache=None, cur_len=None, pos_offset=0,
                prefix_len=None, rt: Runtime = Runtime()):
    """scan over the stacked layer axis.  With ``cache`` each layer reads
    its slice of the stacked K/V and the scan emits only the new tokens'
    K/V, (L, B, Tq, Hkv, hd), for ``decode_step`` to write."""

    def body(carry, scanned):
        h = carry
        if cache is None:
            p = scanned
            h2, _, probs = _block(cfg, p, h, pos_offset=pos_offset,
                                  prefix_len=prefix_len, rt=rt)
            return h2, probs
        p, (ck, cv) = scanned
        h2, (k, v), probs = _block(cfg, p, h, cache_kv=(ck, cv),
                                   cur_len=cur_len, rt=rt)
        return h2, (k, v, probs)

    if rt.remat == "save_a2a":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "moe_a2a"))
    elif rt.remat != "none":
        body = jax.checkpoint(body)

    if cache is None:
        x, probs = jax.lax.scan(body, x, layers)
        return x, None, probs
    x, (k, v, probs) = jax.lax.scan(body, x, (layers, cache))
    return x, (k, v), probs


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
    x, _, probs = _run_layers(cfg, params["layers"], x,
                              prefix_len=prefix_len, rt=rt)
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


def init_cache(cfg, batch_size: int, max_len: int, dtype=None):
    dtype = dtype or DTYPES[cfg.dtype]
    hd, nkv, L = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.n_layers
    return {
        "k": jnp.zeros((L, batch_size, max_len, nkv, hd), dtype),
        "v": jnp.zeros((L, batch_size, max_len, nkv, hd), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg):
    """Logical axes for the cache pytree (sequence is model-sharded for
    decode — flash-decoding style; DESIGN.md §5)."""
    kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": kv, "v": kv, "len": ()}


def prefill(cfg, params, batch, max_len: int, rt: Runtime = Runtime()):
    """Run the prompt, fill a KV cache, return (last-token logits, cache)."""
    x, prefix_len = _embed_tokens(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed_act"))
    bsz, seq = x.shape[0], x.shape[1]
    cache = init_cache(cfg, bsz, max_len)

    def body(carry, scanned):
        h = carry
        p = scanned
        h2, kv, _ = _block(cfg, p, h, prefix_len=prefix_len, rt=rt)
        return h2, kv

    body_fn = jax.checkpoint(body) if rt.remat != "none" else body
    x, (ks, vs) = jax.lax.scan(body_fn, x, params["layers"])
    pad = max_len - seq
    ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": ks, "v": vs, "len": jnp.int32(seq)}
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
    x, (k, v), _ = _run_layers(cfg, params["layers"], x,
                               cache=(cache["k"], cache["v"]), cur_len=cur,
                               rt=rt)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, x)
    at = (0, 0, cur, 0, 0)
    new_cache = {"k": jax.lax.dynamic_update_slice(cache["k"], k, at),
                 "v": jax.lax.dynamic_update_slice(cache["v"], v, at),
                 "len": cur + 1}
    return logits[:, 0], new_cache
