"""Activation-sharding constraints via a trace-time context.

GSPMD propagates parameter shardings into activations, but for FSDP-style
layouts the propagated choice is often wrong (e.g. activations inherit the
d_model/data sharding from the embedding instead of batch/data — observed
directly in the qwen3-14b dry-run: unsharded (B, H, S, S) attention temps).
Production frameworks anchor activations with explicit constraints; models
here call ``constrain(x, logical_axes)`` at block boundaries. Outside an
``activation_sharding(mesh, rules)`` context the call is a no-op, so the
same model code runs in single-device tests unchanged.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import NamedSharding

from .specs import Rules, resolve_spec

__all__ = ["activation_sharding", "constrain", "current_mesh", "sharded"]

_CTX: contextvars.ContextVar = contextvars.ContextVar("act_sharding",
                                                      default=None)


@contextlib.contextmanager
def activation_sharding(mesh, rules: Rules):
    """Enable activation constraints for everything traced inside."""
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


def current_mesh():
    """Mesh of the active activation-sharding context (None outside)."""
    ctx = _CTX.get()
    return ctx[0] if ctx is not None else None


def constrain(x: jax.Array, logical: tuple[str, ...]) -> jax.Array:
    """Apply with_sharding_constraint per the active (mesh, rules); no-op
    outside the context or for mismatched ranks (defensive)."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(logical) != x.ndim:
        return x
    spec = resolve_spec(tuple(x.shape), logical, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def sharded(shape: tuple[int, ...], logical: tuple[str, ...]) -> bool:
    """Whether the active (mesh, rules) split a tensor of ``shape`` with
    these logical axes over more than one device (False outside the
    context)."""
    ctx = _CTX.get()
    if ctx is None:
        return False
    mesh, rules = ctx
    return any(part is not None
               for part in resolve_spec(tuple(shape), logical, rules, mesh))
