"""Unified model API + registry.

``get_model(cfg)`` returns a ``Model`` facade with a family-appropriate
backend. All entry points are functional (params are explicit pytrees) so
they compose with jit/pjit, grad, and the checkpointing substrate.

``input_specs(shape)`` produces ShapeDtypeStruct stand-ins for every input of
the step the shape implies (train_step / prefill / serve_step) — the same
pattern the multi-pod dry-run lowers against, with no device allocation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import rwkv6, transformer, zamba2
from .common import DTYPES
from .transformer import Runtime

__all__ = ["Model", "Runtime", "get_model"]

_BACKENDS = {
    "dense": transformer, "moe": transformer, "mla": transformer,
    "audio": transformer, "vlm": transformer,
    "ssm": rwkv6,
    "hybrid": zamba2,
}


@dataclass(frozen=True)
class Model:
    cfg: Any
    backend: Any

    # ------------------------------------------------------------ factory
    def init(self, key: jax.Array):
        """Returns (params, logical-axis specs)."""
        return self.backend.init(self.cfg, key)

    def param_specs(self):
        """Logical-axis spec tree WITHOUT allocating parameters.

        ``init`` is traced under ``eval_shape`` (no allocation even for the
        235B config); the spec tree — plain string tuples built at trace
        time — is captured as a side effect.
        """
        captured = {}

        def f(k):
            params, specs = self.backend.init(self.cfg, k)
            captured["specs"] = specs
            return params

        jax.eval_shape(f, jax.random.PRNGKey(0))
        return captured["specs"]

    def param_shapes(self):
        """ShapeDtypeStruct tree of the parameters (no allocation)."""
        return jax.eval_shape(
            lambda k: self.backend.init(self.cfg, k)[0], jax.random.PRNGKey(0))

    # ------------------------------------------------------------- steps
    def train_loss(self, params, batch, rt: Runtime = Runtime()):
        return self.backend.train_loss(self.cfg, params, batch, rt)

    def forward(self, params, batch, rt: Runtime = Runtime()):
        return self.backend.forward(self.cfg, params, batch, rt)

    def prefill(self, params, batch, max_len: int, rt: Runtime = Runtime()):
        return self.backend.prefill(self.cfg, params, batch, max_len, rt)

    def decode_step(self, params, batch, cache, rt: Runtime = Runtime()):
        return self.backend.decode_step(self.cfg, params, batch, cache, rt)

    def init_cache(self, batch_size: int, max_len: int):
        return self.backend.init_cache(self.cfg, batch_size, max_len)

    def cache_specs(self):
        return self.backend.cache_specs(self.cfg)

    # ------------------------------------------------------- shape specs
    def input_specs(self, shape) -> dict:
        """ShapeDtypeStructs for the batch of `shape` (see configs.SHAPES)."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        if cfg.family == "audio":
            k = cfg.n_codebooks
            if shape.kind == "decode":
                return {"tokens": sds((b, k, 1), i32)}
            d = {"tokens": sds((b, k, s), i32)}
            if shape.kind == "train":
                d["targets"] = sds((b, k, s), i32)
            return d

        if cfg.family == "vlm":
            p, vd = cfg.n_patches, cfg.vision_embed_dim
            text = s - p
            assert text > 0, "vlm sequence must exceed the patch prefix"
            if shape.kind == "decode":
                return {"tokens": sds((b, 1), i32)}
            d = {"patches": sds((b, p, vd), DTYPES[cfg.dtype]),
                 "tokens": sds((b, text), i32)}
            if shape.kind == "train":
                d["targets"] = sds((b, text), i32)
            return d

        if shape.kind == "decode":
            return {"tokens": sds((b, 1), i32)}
        d = {"tokens": sds((b, s), i32)}
        if shape.kind == "train":
            d["targets"] = sds((b, s), i32)
        return d

    def cache_input_specs(self, shape) -> dict:
        """ShapeDtypeStructs for a filled cache at ``shape`` (decode only)."""
        cache = jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len))
        return cache


def get_model(cfg) -> Model:
    try:
        backend = _BACKENDS[cfg.family]
    except KeyError as e:
        raise KeyError(f"no backend for family '{cfg.family}'") from e
    return Model(cfg, backend)
