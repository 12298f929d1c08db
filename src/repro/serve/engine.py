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
    rows) over every batch served; a model without them leaves it empty.
    ``steps`` counts decode steps, and ``steps_ahead`` those at which the
    host came to read the previous step's tokens before the device had
    them, over every batch served."""

    def __init__(self, model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.counters: dict[str, int] = {}
        self.steps = 0
        self.steps_ahead = 0
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cfg.max_len, cfg.rt))
        # The cache is donated: the step writes one position into it in
        # place, and ``generate_batch`` never reads a cache it passed in.
        self._decode = jax.jit(
            lambda p, b, c: model.decode_step(p, b, c, cfg.rt),
            donate_argnums=(2,))
        # A program of its own, so that the decode program compiles as it
        # would without it.
        self._argmax = jax.jit(
            lambda logits: jnp.argmax(logits, -1, keepdims=True)
            .astype(jnp.int32))

    def _sample(self, logits: jax.Array, rng: np.random.Generator):
        """(B, V) logits -> the (B, 1) int32 tokens ``_decode`` takes.

        Greedy selection stays on the device: ``jnp.argmax``, like
        ``np.argmax``, takes the first of equal maxima. Sampling at a
        temperature copies the logits to the host and draws from ``rng``."""
        if self.cfg.temperature <= 0:
            return self._argmax(logits)
        z = np.asarray(logits, np.float32) / self.cfg.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return jnp.asarray([[rng.choice(p.shape[-1], p=row)] for row in p],
                           jnp.int32)

    def generate_batch(self, prompts: np.ndarray, max_new: int,
                       eos_id: int | None = None, seed: int = 0):
        """One batch of same-length prompts -> (B, <=max_new) generations.

        The host reads each step's tokens one step behind: step t
        dispatches the decode of token t and the selection of token t+1,
        and only then reads token t, so the device has the next step
        queued while the host waits and copies. Under greedy selection no
        logits leave the device. The first token is read before the first
        decode. ``max_new`` decodes are dispatched, the last one's logits
        unsampled; an ``eos_id`` stop dispatches one decode past the step
        at which every sequence ended, and drops its token.

        Profiler spans: ``mt4g.serve.prefill`` (the prompt batch and the
        prefill dispatch); per token ``mt4g.serve.decode`` (the decode
        dispatch), ``mt4g.serve.sample`` (the selection's dispatch, or the
        logits' copy and the draw at a temperature) and
        ``mt4g.serve.fetch`` (the wait for a step's B tokens and their
        copy to the host); once after the last token,
        ``mt4g.serve.counters`` (the wait for the last step and the copy
        of the model's counters), for a model that keeps counters."""
        rng = np.random.default_rng(seed)
        with span("mt4g.serve.prefill"):
            batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
            logits, cache = self._prefill(self.params, batch)
        with span("mt4g.serve.sample"):
            tok = self._sample(logits, rng)
        outs = []
        alive = np.ones(prompts.shape[0], bool)

        def read(tok) -> bool:
            """Copy a step's tokens out; True once every sequence ended."""
            with span("mt4g.serve.fetch"):
                host = np.asarray(tok)[:, 0]
            outs.append(host)
            if eos_id is None:
                return False
            alive[host == eos_id] = False
            return not alive.any()

        ended = read(tok)
        for t in range(max_new):
            if ended:
                break
            fed = tok
            with span("mt4g.serve.decode"):
                logits, cache = self._decode(self.params, {"tokens": fed},
                                             cache)
            self.steps += 1
            if t + 1 < max_new:
                with span("mt4g.serve.sample"):
                    tok = self._sample(logits, rng)
            if t:
                self.steps_ahead += not fed.is_ready()
                ended = read(fed)
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
