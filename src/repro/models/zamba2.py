"""Zamba2 hybrid — Mamba2 backbone + one *shared* attention block
(zamba2-2.7b: 54 mamba layers; the shared block fires every 6 layers).

Faithful-to-family structure: the shared transformer block has ONE set of
attention+MLP weights; each application site concatenates the current hidden
state with the original embedding ([h; emb] -> 2d) and maps it through a
per-site input projector, per the Zamba2 design. KV caches exist only at the
shared-block sites, which is what makes long_500k viable for this family.
The shared block's attention is ``gqa``'s; its cache, ``cache["attn"]``, is
a ``gqa`` cache with one entry a site, read in place by the decode step and
written once after it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import gqa
from .common import (DTYPES, ParamBuilder, cross_entropy, rms_norm,
                     stack_layers, swiglu)
from .mamba2 import _dims, init_mamba2, mamba2_seq, mamba2_step
from ..sharding.context import constrain

__all__ = ["init", "train_loss", "prefill", "decode_step", "init_cache"]


def _n_sites(cfg) -> int:
    return (cfg.n_layers + cfg.shared_attn_every - 1) // cfg.shared_attn_every


def _init_shared_block(b: ParamBuilder, cfg) -> None:
    d = cfg.d_model
    b.add("ln1", (d,), ("embed",), init="ones")
    gqa.init_attn(b, cfg)
    b.add("ln2", (d,), ("embed",), init="ones")
    b.add("w1", (d, cfg.d_ff), ("embed", "ff"))
    b.add("w3", (d, cfg.d_ff), ("embed", "ff"))
    b.add("w2", (cfg.d_ff, d), ("ff", "embed"))


def init(cfg, key: jax.Array):
    dtype = DTYPES[cfg.dtype]
    b = ParamBuilder(key, dtype)
    d = cfg.d_model
    b.add("embed", (cfg.vocab_size, d), ("vocab", "embed"))
    b.add("head", (d, cfg.vocab_size), ("embed", "vocab"))
    b.add("final_norm", (d,), ("embed",), init="ones")
    _init_shared_block(b.sub("shared"), cfg)

    n_sites = _n_sites(cfg)
    # Per-site [h; emb] -> d input projectors for the shared block.
    b.add("site_proj", (n_sites, 2 * d, d), ("sites", "embed2", "embed"))

    layers, lspecs = stack_layers(b._next("layers"), cfg.n_layers,
                                  lambda lb: init_mamba2(lb, cfg), dtype)
    params, specs = b.build()
    params["layers"], specs["layers"] = layers, lspecs
    return params, specs


# ---------------------------------------------------------------- shared
def _shared_attn(cfg, sp, site_proj, h, emb, *, cache=None, cur_len=None,
                 q_chunk=1024):
    """One application of the shared block at a site.  Without ``cache``
    the attention runs over the sequence; with it (decode) the new token
    attends to the site's cache, read in place.  Returns (out, the tokens'
    cache entries)."""
    x = jnp.concatenate([h, emb], axis=-1) @ site_proj       # (B,T,d)
    a = rms_norm(x, sp["ln1"], cfg.norm_eps)
    if cache is None:
        out, rows = gqa.attend(cfg, sp, a, q_chunk=q_chunk)
    else:
        out, rows = gqa.attend_cached(cfg, sp, a, cache, cur_len)
    x = x + out
    m = rms_norm(x, sp["ln2"], cfg.norm_eps)
    x = x + swiglu(m, sp["w1"], sp["w3"], sp["w2"])
    return x, rows


# --------------------------------------------------------------- sequence
def _group_layers(cfg, layers):
    """Reshape the (L, ...) stack into (sites, every, ...) for a 2-level scan."""
    every = cfg.shared_attn_every
    n_sites = _n_sites(cfg)
    pad = n_sites * every - cfg.n_layers
    assert pad == 0, "n_layers must be divisible by shared_attn_every"
    return jax.tree.map(
        lambda a: a.reshape((n_sites, every) + a.shape[1:]), layers)


def _run_seq(cfg, params, x, remat=False, q_chunk=1024):
    emb = x
    grouped = _group_layers(cfg, params["layers"])

    def outer_body(h, xs):
        site_proj, group = xs
        h = constrain(h, ("batch", "seq", "embed_act"))
        shared_out, _ = _shared_attn(cfg, params["shared"], site_proj, h, emb,
                                     q_chunk=q_chunk)
        h = h + shared_out               # shared block feeds the residual

        def inner_body(hh, lp):
            y, _, _ = mamba2_seq(lp, hh, cfg)
            return constrain(hh + y, ("batch", "seq", "embed_act")), None

        fn = jax.checkpoint(inner_body) if remat else inner_body
        h, _ = jax.lax.scan(fn, h, group)
        return h, None

    h, _ = jax.lax.scan(outer_body, x, (params["site_proj"], grouped))
    return h, None


def forward(cfg, params, batch, rt=None):
    remat = (getattr(rt, "remat", "none") != "none") if rt else False
    q_chunk = getattr(rt, "q_chunk", 1024) if rt else 1024
    x = params["embed"][batch["tokens"]]
    x = constrain(x, ("batch", "seq", "embed_act"))
    h, _ = _run_seq(cfg, params, x, remat=remat, q_chunk=q_chunk)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return constrain(h @ params["head"], ("batch", "seq", "vocab")), None


def train_loss(cfg, params, batch, rt=None):
    logits, _ = forward(cfg, params, batch, rt)
    return cross_entropy(logits, batch["targets"])


# ------------------------------------------------------------------ serve
def init_cache(cfg, batch_size: int, max_len: int, dtype=None):
    dtype = dtype or DTYPES[cfg.dtype]
    d_in, h, pp, n = _dims(cfg)
    conv_dim = d_in + 2 * n
    return {
        "attn": gqa.init_cache(cfg, _n_sites(cfg), batch_size, max_len,
                               dtype),
        "ssm": jnp.zeros((cfg.n_layers, batch_size, h, pp, n), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch_size, cfg.ssm_conv_width - 1,
                           conv_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }


def cache_specs(cfg):
    return {"attn": gqa.CACHE_AXES,
            "ssm": ("layers", "batch", "state_heads", "head_dim", "state"),
            "conv": ("layers", "batch", "conv", "inner"),
            "len": ()}


def prefill(cfg, params, batch, max_len: int, rt=None):
    q_chunk = getattr(rt, "q_chunk", 1024) if rt else 1024
    x = params["embed"][batch["tokens"]]
    emb = x
    t = x.shape[1]
    grouped = _group_layers(cfg, params["layers"])

    def outer_body(h, xs):
        site_proj, group = xs
        shared_out, rows = _shared_attn(cfg, params["shared"], site_proj, h,
                                        emb, q_chunk=q_chunk)
        h = h + shared_out

        def inner_body(hh, lp):
            y, ssm, conv = mamba2_seq(lp, hh, cfg)
            return hh + y, (ssm, conv)

        h, (ssm, conv) = jax.lax.scan(inner_body, h, group)
        return h, (rows, ssm, conv)

    h, (rows, ssm, conv) = jax.lax.scan(outer_body, x,
                                        (params["site_proj"], grouped))
    pad = ((0, 0), (0, 0), (0, max_len - t), (0, 0), (0, 0))
    L = cfg.n_layers
    cache = {
        "attn": jax.tree.map(lambda a: jnp.pad(a, pad), rows),
        "ssm": ssm.reshape((L,) + ssm.shape[2:]),
        "conv": conv.reshape((L,) + conv.shape[2:]),
        "len": jnp.int32(t),
    }
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return h[:, -1] @ params["head"], cache


def decode_step(cfg, params, batch, cache, rt=None):
    x = params["embed"][batch["tokens"]][:, 0]          # (B,d)
    emb = x
    cur = cache["len"]
    every = cfg.shared_attn_every
    n_sites = _n_sites(cfg)
    grouped = _group_layers(cfg, params["layers"])
    ssm_g = cache["ssm"].reshape((n_sites, every) + cache["ssm"].shape[1:])
    conv_g = cache["conv"].reshape((n_sites, every) + cache["conv"].shape[1:])

    def outer_body(h, xs):
        site_proj, group, site_cache, ssm, conv = xs
        h2, new = _shared_attn(cfg, params["shared"], site_proj, h[:, None],
                               emb[:, None], cache=site_cache, cur_len=cur)
        h = h + h2[:, 0]

        def inner_body(hh, xs2):
            lp, s, cs = xs2
            y, s2, cs2 = mamba2_step(lp, hh, cfg, s, cs)
            return hh + y, (s2, cs2)

        h, (ssm2, conv2) = jax.lax.scan(inner_body, h, (group, ssm, conv))
        return h, (new, ssm2, conv2)

    h, (new, ssm, conv) = jax.lax.scan(
        outer_body, x,
        (params["site_proj"], grouped, cache["attn"], ssm_g, conv_g))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = h @ params["head"]
    L = cfg.n_layers
    new_cache = {
        "attn": gqa.write(cache["attn"], new, cur),
        "ssm": ssm.reshape((L,) + ssm.shape[2:]),
        "conv": conv.reshape((L,) + conv.shape[2:]),
        "len": cur + 1,
    }
    return logits, new_cache
