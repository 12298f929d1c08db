"""Expert-parallel MoE FFN under shard_map (beyond-paper optimization).

The GSPMD lowering of the sorted-dispatch MoE (moe.py) falls back to
"scatter = materialize + all-reduce": the full (E*cap, d) buffer is
all-reduced across the data axis per layer, ~24 TB/device/step on the
qwen3-moe-30b train cell (EXPERIMENTS.md §Perf, hillclimb B).

This module routes tokens explicitly:

  1. per shard: top-k routing, destination shard = expert // E_local;
  2. pack tokens into per-destination slots (static capacity C_send);
  3. ``lax.all_to_all`` over the data axis (the EP axis — expert weights are
     sharded over it);
  4. local capacity dispatch to the shard's E_local experts, batched
     matmuls (ff dim sharded over "model" -> one psum at the end);
  5. reverse all-to-all (an involution: rows return to their send slots),
     weight and combine at the source.

Collective bytes per layer drop to 2 x (tokens x d) a2a + one d-sized psum —
the algorithmic minimum for EP — instead of E*cap*d all-reduces.
"""
from __future__ import annotations

import math

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["moe_ffn_ep"]


def _positions_within_groups(group_ids: jax.Array, n_groups: int,
                             length: int) -> jax.Array:
    """Rank of each element within its group, computed via stable sort."""
    order = jnp.argsort(group_ids, stable=True)
    sorted_g = group_ids[order]
    start = jnp.searchsorted(sorted_g, sorted_g, side="left")
    rank_sorted = jnp.arange(length) - start
    ranks = jnp.zeros(length, jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    return ranks


def _ep_body(x, router, w1, w3, w2, *, cfg, dp_axes, ep_axis, tp_axis, dsz):
    """shard_map body. x (B_loc, S, d); w* sharded: E over ep, ff over tp."""
    b_loc, s, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    e_loc = e // dsz
    t = b_loc * s
    cf = cfg.moe_capacity_factor

    xt = x.reshape(t, d)
    logits = (xt @ router).astype(jnp.float32)             # (T, E) full router
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    pairs = t * k
    flat_e = top_e.reshape(pairs)
    flat_w = top_w.reshape(pairs).astype(x.dtype)
    token_id = jnp.repeat(jnp.arange(t), k)

    # ---- pack into per-destination-shard slots
    dest = flat_e // e_loc                                  # (pairs,)
    c_send = max(int(math.ceil(pairs / dsz * cf)), k)
    pos = _positions_within_groups(dest, dsz, pairs)
    keep = pos < c_send
    slot = jnp.where(keep, dest * c_send + pos, dsz * c_send)

    send_x = jnp.zeros((dsz * c_send + 1, d), x.dtype).at[slot].set(xt[token_id])
    send_e = jnp.full((dsz * c_send + 1,), e, jnp.int32).at[slot].set(
        flat_e % e_loc)                                     # local expert id
    send_x = send_x[:-1].reshape(dsz, c_send, d)
    send_e = send_e[:-1].reshape(dsz, c_send)

    # ---- exchange: row block i goes to shard i
    recv_x = jax.lax.all_to_all(send_x, ep_axis, split_axis=0, concat_axis=0,
                                tiled=False)
    # Named for the save_a2a remat policy: saving the received activations
    # keeps the backward from replaying the forward exchange.
    recv_x = jax.ad_checkpoint.checkpoint_name(recv_x, "moe_a2a")
    recv_e = jax.lax.all_to_all(send_e, ep_axis, split_axis=0, concat_axis=0,
                                tiled=False)
    rt = dsz * c_send
    rx = recv_x.reshape(rt, d)
    re = recv_e.reshape(rt)                                 # in [0, e_loc] (e_loc==invalid)

    # ---- local capacity dispatch to my e_loc experts
    c_loc = max(int(math.ceil(rt / e_loc * cf)), 1)
    lpos = _positions_within_groups(re, e_loc + 1, rt)
    lkeep = (re < e_loc) & (lpos < c_loc)
    lslot = jnp.where(lkeep, re * c_loc + lpos, e_loc * c_loc)
    buf = jnp.zeros((e_loc * c_loc + 1, d), x.dtype).at[lslot].set(rx)
    buf = buf[:-1].reshape(e_loc, c_loc, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) \
        * jnp.einsum("ecd,edf->ecf", buf, w3)
    out = jnp.einsum("ecf,efd->ecd", h, w2).reshape(e_loc * c_loc, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), x.dtype)], axis=0)

    # ---- return rows to their send slots (a2a is an involution here)
    back = (out[lslot] * lkeep[:, None].astype(x.dtype)).reshape(
        dsz, c_send, d)
    ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0,
                             tiled=False).reshape(dsz * c_send, d)
    ret = jax.ad_checkpoint.checkpoint_name(ret, "moe_a2a")
    ret = jnp.concatenate([ret, jnp.zeros((1, d), x.dtype)], axis=0)

    # ---- weight + combine at the source
    y_pairs = ret[slot] * (flat_w * keep.astype(x.dtype))[:, None]
    y = jnp.zeros((t, d), x.dtype).at[token_id].add(y_pairs)
    # ff was sharded over the tensor-parallel axis -> partial sums.
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y.reshape(b_loc, s, d), probs


def moe_ffn_ep(p, x: jax.Array, cfg, mesh) -> tuple[jax.Array, jax.Array]:
    """moe.moe_ffn's routing with explicit EP collectives (needs a mesh):
    (out, router probs).  Its send slots have a capacity
    (``cfg.moe_capacity_factor``), and it keeps no counters."""
    names = mesh.axis_names
    ep_axis = "data" if "data" in names else names[-1]
    tp_axis = "model" if "model" in names else None
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    batch_entry = dp_axes if len(dp_axes) > 1 else \
        (dp_axes[0] if dp_axes else None)

    body = lambda xx, r, a, b, c: _ep_body(
        xx, r, a, b, c, cfg=cfg, dp_axes=dp_axes, ep_axis=ep_axis,
        tp_axis=tp_axis, dsz=int(mesh.shape[ep_axis]))
    y, probs = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_entry, None, None),         # x: batch over DP
                  P(None, None),                      # router: replicated
                  P(ep_axis, None, tp_axis),          # w1 (E, d, ff)
                  P(ep_axis, None, tp_axis),          # w3 (E, d, ff)
                  P(ep_axis, tp_axis, None)),         # w2 (E, ff, d)
        out_specs=(P(batch_entry, None, None),
                   P(batch_entry, None)),
        check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])
    return y, probs.reshape(-1, probs.shape[-1])
