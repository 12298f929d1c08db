"""Decoder-only transformer driver for the dense / moe / mla / audio / vlm
families.

One implementation covers:
  * dense GQA (+ optional qk_norm) — qwen3-14b/32b, codeqwen1.5-7b, internlm2
    (gqa.py);
  * MoE FFN — qwen3-moe-30b/235b (see moe.py);
  * latent attention with an expert FFN after leading dense layers —
    deepseek-v2-lite (mla.py, moe.py); its cache holds the latent and the
    rotary key a position, and its expert layers count the pairs they
    route;
  * multi-codebook audio LM — musicgen (sum-of-codebook embeddings, K heads);
  * prefix-LM VLM — paligemma (stub patch embeddings + projector, MQA,
    logit soft-capping, sqrt(d) embedding scale).

The attention is a module picked once from the config (``_attn_module``):
``mla`` for the latent family, ``gqa`` for every other.  Both have one
interface — ``init_attn``, ``init_cache``/``CACHE_AXES``/``write`` for the
cache's layout, ``attend`` for a whole sequence and ``attend_cached`` for
new tokens against the cache — so nothing below branches on the family's
attention.

Layers are stacked on a leading "layers" axis and executed with
``jax.lax.scan`` (optionally rematerialized) so trace/compile cost is O(1)
in depth.  Leading dense layers (``cfg.dense_layers``) are a stack of their
own, ``params["dense_layers"]``, scanned before ``params["layers"]``.  The
cache has one entry a stack, under the stack's name, in the attention
module's layout, next to ``len`` and the expert layers' counters.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .common import (DTYPES, ParamBuilder, cross_entropy, rms_norm,
                     stack_layers, swiglu)
from ..sharding.context import constrain
from . import gqa, mla
from .moe import init_moe, load_balance_loss, moe_ffn

__all__ = ["Runtime", "init", "forward", "train_loss", "prefill",
           "decode_step", "init_cache"]


@dataclass(frozen=True)
class Runtime:
    """Execution knobs (perf levers — see EXPERIMENTS.md §Perf)."""

    q_chunk: int = 1024          # query-chunked attention threshold
    remat: str = "none"          # none | full — scan-level rematerialization
    moe_aux_weight: float = 0.01


COUNTERS = ("moe_pairs", "moe_rows")


def _attn_module(cfg):
    """The attention: latent (``mla``) for that family, GQA otherwise."""
    return mla if cfg.family == "mla" else gqa


# ------------------------------------------------------------------- init
def _init_layer(b: ParamBuilder, cfg, experts: bool) -> None:
    d = cfg.d_model
    b.add("ln1", (d,), ("embed",), init="ones")
    _attn_module(cfg).init_attn(b, cfg)
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
def _block(cfg, p, x, *, cache=None, cur_len=None, prefix_len=None,
           rt: Runtime = Runtime()):
    """One layer.  Without ``cache`` (train / prefill) the attention runs
    over the sequence; with it (decode) the new tokens attend to the
    layer's cache, read in place.  Returns (out, the tokens' cache entries,
    router probs, the expert layer's counters or None)."""
    attn = _attn_module(cfg)
    x = constrain(x, ("batch", "seq", "embed_act"))
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cache is None:
        attn_out, rows = attn.attend(cfg, p, h, prefix_len=prefix_len,
                                     q_chunk=rt.q_chunk)
    else:
        attn_out, rows = attn.attend_cached(cfg, p, h, cache, cur_len)
    x = x + attn_out
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    counts = None
    if "moe" in p:
        ffn_out, router_probs, counts = moe_ffn(p["moe"], h, cfg)
    else:
        ffn_out = swiglu(h, p["w1"], p["w3"], p["w2"])
        router_probs = jnp.zeros((1, 1), jnp.float32)
    out = constrain(x + ffn_out, ("batch", "seq", "embed_act"))
    return out, rows, router_probs, counts


def _run_layers(cfg, layers, x, *, cache=None, cur_len=None,
                prefix_len=None, rt: Runtime = Runtime()):
    """scan over the stacked layer axis.  With ``cache`` each layer reads
    its slice of the stack's cache and the scan emits only the new tokens'
    entries, e.g. (L, B, Tq, Hkv, hd), for ``decode_step`` to write.
    Returns (x, those entries or None, router probs, per-layer counters or
    None)."""

    def body(carry, scanned):
        h = carry
        if cache is None:
            p = scanned
            h2, _, probs, counts = _block(cfg, p, h, prefix_len=prefix_len,
                                          rt=rt)
            return h2, (probs, counts)
        p, layer_cache = scanned
        h2, new, probs, counts = _block(cfg, p, h, cache=layer_cache,
                                        cur_len=cur_len, rt=rt)
        return h2, (new, probs, counts)

    if rt.remat != "none":
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
    """Zeros in the cache's layout: a layer stack's entry is the attention
    module's (GQA: ``k``/``v`` (L, B, S, Hkv, hd); MLA: the latent rows)."""
    dtype = dtype or DTYPES[cfg.dtype]
    attn = _attn_module(cfg)
    cache = {name: attn.init_cache(cfg, n, batch_size, max_len, dtype)
             for name, n in _stack_sizes(cfg).items()}
    return {**cache, "len": jnp.zeros((), jnp.int32), **_zero_counters(cfg)}


def cache_specs(cfg):
    """Logical axes for the cache pytree (sequence is model-sharded for
    decode — flash-decoding style; DESIGN.md §5)."""
    counters = {"counters": {k: () for k in COUNTERS}} \
        if cfg.moe_experts else {}
    axes = _attn_module(cfg).CACHE_AXES
    return {**{n: axes for n in _stack_sizes(cfg)}, "len": (), **counters}


def prefill(cfg, params, batch, max_len: int, rt: Runtime = Runtime()):
    """Run the prompt, fill a KV cache, return (last-token logits, cache)."""
    x, prefix_len = _embed_tokens(cfg, params, batch)
    x = constrain(x, ("batch", "seq", "embed_act"))
    seq = x.shape[1]

    def body(carry, scanned):
        h = carry
        p = scanned
        h2, rows, _, counts = _block(cfg, p, h, prefix_len=prefix_len,
                                     rt=rt)
        return h2, (rows, counts)

    def padded(a):                       # (L, B, seq, ...) -> max_len
        return jnp.pad(a, ((0, 0), (0, 0), (0, max_len - seq))
                       + ((0, 0),) * (a.ndim - 3))

    body_fn = jax.checkpoint(body) if rt.remat != "none" else body
    cache = {"len": jnp.int32(seq), **_zero_counters(cfg)}
    for name, layers in _stacks(cfg, params):
        x, (rows, counts) = jax.lax.scan(body_fn, x, layers)
        cache[name] = jax.tree.map(padded, rows)
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
        x, written[name], _, counts = _run_layers(cfg, layers, x,
                                                  cache=cache[name],
                                                  cur_len=cur, rt=rt)
        if "counters" in new_cache:
            new_cache["counters"] = _count(new_cache["counters"], counts)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, x)
    attn = _attn_module(cfg)
    for name, new in written.items():
        new_cache[name] = attn.write(cache[name], new, cur)
    new_cache["len"] = cur + 1
    return logits[:, 0], new_cache
