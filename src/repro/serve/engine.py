"""Token-serving engine: batched prefill + decode with continuous batching.

Naming note — this repo has three "engines", and this is the *model* one:
``core/engine`` is the probe engine behind the unified
``discover(request)`` core, ``serve/jobs.JobEngine`` is the remote
discovery job engine behind ``POST /discoveries``, and this module serves
LLM tokens for the latency benchmarks.  It shares nothing with the other
two beyond the name.

The engine owns a fixed pool of B sequence slots. ``generate`` services a
request list: prompts are prefilled into free slots, every ``step`` decodes
all active slots at once (one jitted serve_step), finished sequences retire
and their slots are immediately refilled — the standard continuous-batching
loop, minus speculative niceties.

For multi-device serving the same jitted functions are used with the SERVE
sharding rules (sequence-parallel KV cache over "model").
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..models import Runtime
from ..tracing import span

__all__ = ["ServeConfig", "Engine"]


@dataclass(frozen=True)
class ServeConfig:
    """Serving knobs: slot-pool size, sequence cap, sampling temperature."""

    max_len: int = 256
    slots: int = 4
    temperature: float = 0.0        # 0 -> greedy
    rt: Runtime = Runtime(q_chunk=0)


class Engine:
    """Continuous-batching token server over a fixed slot pool; the
    ``generate`` loop prefills into free slots and decodes all active
    slots per step with one jitted call.

    ``counters`` sums the counters a model keeps in its cache
    (``cache["counters"]``: an expert layer's routed pairs and computed
    rows) over every batch served; a model without them leaves it empty."""

    def __init__(self, model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.counters: dict[str, int] = {}
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cfg.max_len, cfg.rt))
        # The cache is donated: the step writes one position into it in
        # place, and ``generate_batch`` never reads a cache it passed in.
        self._decode = jax.jit(
            lambda p, b, c: model.decode_step(p, b, c, cfg.rt),
            donate_argnums=(2,))

    def _sample(self, logits: np.ndarray, rng: np.random.Generator):
        if self.cfg.temperature <= 0:
            return np.argmax(logits, axis=-1)
        z = logits / self.cfg.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return np.array([rng.choice(p.shape[-1], p=row) for row in p])

    def generate_batch(self, prompts: np.ndarray, max_new: int,
                       eos_id: int | None = None, seed: int = 0):
        """One batch of same-length prompts -> (B, <=max_new) generations.

        Profiler spans: ``mt4g.serve.prefill`` (the prompt batch and the
        prefill dispatch); per token ``mt4g.serve.fetch`` (the wait for the
        step and the logits' copy to the host), ``mt4g.serve.sample`` and
        ``mt4g.serve.decode`` (the token batch and the decode dispatch);
        once after the last token, ``mt4g.serve.counters`` (the wait for
        the last step and the copy of the model's counters), for a model
        that keeps counters."""
        rng = np.random.default_rng(seed)
        with span("mt4g.serve.prefill"):
            batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
            logits, cache = self._prefill(self.params, batch)
        outs = []
        alive = np.ones(prompts.shape[0], bool)
        for _ in range(max_new):
            with span("mt4g.serve.fetch"):
                host = np.asarray(logits, np.float32)
            with span("mt4g.serve.sample"):
                nxt = self._sample(host, rng)
            outs.append(nxt)
            if eos_id is not None:
                alive &= nxt != eos_id
                if not alive.any():
                    break
            with span("mt4g.serve.decode"):
                logits, cache = self._decode(
                    self.params,
                    {"tokens": jnp.asarray(nxt[:, None], jnp.int32)}, cache)
        if "counters" in cache:
            with span("mt4g.serve.counters"):
                for k, v in jax.device_get(cache["counters"]).items():
                    self.counters[k] = self.counters.get(k, 0) + int(v)
        return np.stack(outs, axis=1)

    def serve(self, requests: list[np.ndarray], max_new: int,
              seed: int = 0) -> list[np.ndarray]:
        """Continuous batching over a request queue (equal-length prompts
        grouped into slot-sized waves)."""
        results: dict[int, np.ndarray] = {}
        queue = list(enumerate(requests))
        while queue:
            wave = queue[: self.cfg.slots]
            queue = queue[self.cfg.slots:]
            ids = [i for i, _ in wave]
            prompts = np.stack([p for _, p in wave])
            gen = self.generate_batch(prompts, max_new, seed=seed)
            for j, i in enumerate(ids):
                results[i] = gen[j]
        return [results[i] for i in range(len(requests))]
