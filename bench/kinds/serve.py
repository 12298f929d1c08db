"""Traffic kind ``serve``: a closed queue of equal prompts through
``repro.serve.Engine``.

Parameters (``bench/traffic/<mix>.json``): ``slots`` (the engine's slot
pool, one wave of prompts per ``Engine.serve`` call), ``prompt_len``,
``max_new`` and ``max_len``; ``check_requests``, how many finished
requests the reference recomputes; ``gap_limit``, the widest logit gap a
served token may show.  Prompts are uniform over the vocabulary, drawn
from the seed; every seed serves the same shapes.

End-to-end: ``tokens_per_s``, tokens generated over the window, waves
taken whole (the wave running when the time is up finishes and counts,
with its time).
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from harness.arith import Dense
from harness.common import load_module, seed32

# The control: the reference with its matrices in float8, one step below
# the bfloat16 the configuration states (see the reference's ``fp8``).
CONTROL = {"fp8_control": True}


def _program_config(ctx):
    """The program's model config, at the widths the file states."""
    from repro.configs import get_config

    c = ctx.config
    base = get_config(c["program"]["arch"])
    cfg = base.replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"])
    if ctx.rehearse:
        cfg = cfg.replace(name=cfg.name + "-rehearsal")
    return cfg


class Serving:
    def __init__(self, ctx):
        import jax

        from repro.models import get_model
        from repro.serve import Engine, ServeConfig

        if ctx.rehearse:
            ctx.config = {**ctx.config, **ctx.config["rehearsal"]}
        t = ctx.traffic
        self.ctx = ctx
        self.ref = load_module("configs", f"{ctx.config_name}.py")
        self.slots, self.plen = t["slots"], t["prompt_len"]
        self.max_new, self.max_len = t["max_new"], t["max_len"]
        self.vocab = ctx.config["vocab_size"]
        self.wseed = seed32(ctx.seed, 1)
        cfg = _program_config(ctx)
        model = get_model(cfg)
        want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
        params = self.ref.make_params(ctx.config, self.wseed)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise SystemExit("bench: the program's parameter tree differs "
                             "from the reference's layout")
        self.engine = Engine(model, params, ServeConfig(
            max_len=self.max_len, slots=self.slots))
        self._spans()
        if ctx.faults.get("engine"):
            ctx.faults["engine"](self.engine)
        self.rng = np.random.default_rng(ctx.seed32)
        self.done: list[tuple[np.ndarray, np.ndarray]] = []
        self.failed = 0

    def _spans(self):
        """Name each call into the model's programs on the host."""
        eng, span = self.engine, self.ctx.span
        prefill, decode = eng._prefill, eng._decode

        def traced_prefill(*a):
            with span("bench.prefill"):
                return prefill(*a)

        def traced_decode(*a):
            with span("bench.decode"):
                return decode(*a)

        eng._prefill, eng._decode = traced_prefill, traced_decode

    def wave_prompts(self) -> np.ndarray:
        return self.rng.integers(0, self.vocab, (self.slots, self.plen),
                                 dtype=np.int64).astype(np.int32)

    def serve_wave(self, prompts, max_new) -> list[np.ndarray]:
        with self.ctx.span("bench.wave"):
            return self.engine.serve(list(prompts), max_new=max_new)


def setup(ctx):
    s = Serving(ctx)
    ctx.mark("weights")
    s.serve_wave(s.wave_prompts(), 2)       # compiles prefill and decode
    ctx.mark("warm-up")
    return s


def window(ctx, s: Serving) -> dict:
    t0 = time.perf_counter()
    waves = 0
    while time.perf_counter() - t0 < ctx.seconds:
        prompts = s.wave_prompts()
        try:
            outs = s.serve_wave(prompts, s.max_new)
        except Exception as e:          # noqa: BLE001 — failed requests
            print(f"bench: wave failed: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            s.failed += len(prompts)
            continue
        s.done += list(zip(prompts, outs))
        waves += 1
    wall = time.perf_counter() - t0
    tokens = sum(int(np.asarray(o).size) for _, o in s.done)
    m = Dense.from_config(ctx.config)
    flops = waves * (m.prefill_flops(s.slots, s.plen) + sum(
        m.decode_flops(s.slots, s.plen + j) for j in range(s.max_new)))
    return {"attempted": waves * s.slots + s.failed, "failed": s.failed,
            "wall_s": wall,
            "end_to_end": {"tokens_per_s": tokens / wall},
            "counters": {"waves": waves, "slots": s.slots,
                         "prompt_len": s.plen, "max_new": s.max_new,
                         "generated_tokens": tokens,
                         "decode_steps": waves * s.max_new,
                         "model_flops": flops}}


def checked_requests(n_done: int, slots: int, k: int, seed: int) -> list:
    """``k`` finished requests to compare, drawn from ``seed`` and spread
    over the slot positions: the j-th from the j-th of ``k`` equal bands
    of slots, on even slots for even j and odd slots for odd j where a
    band holds both.  A wave served for only its first or last half, or
    only its even or odd slots, so always leaves out a compared request.
    Indices into ``done``, which holds whole waves in slot order."""
    rng = np.random.default_rng(seed)
    waves = n_done // slots
    chosen: list[int] = []
    for j in range(k):
        lo = (j * slots) // k
        hi = max(((j + 1) * slots) // k, lo + 1)
        band = [w * slots + p for w in range(waves) for p in range(lo, hi)
                if hi - lo < 2 or p % 2 == j % 2]
        free = [i for i in band if i not in chosen]
        if free:
            chosen.append(free[int(rng.integers(len(free)))])
    return sorted(chosen)


def check(ctx, s: Serving) -> dict:
    """The widest gap, over a sample of finished requests drawn from the
    seed, between the reference's best logit and its logit of the token
    served.  The control adds the float8 control's widest gap."""
    control = bool(ctx.faults.get("fp8_control"))
    done = s.done
    s.engine = None                     # frees the program's weights
    gc.collect()
    pick = checked_requests(len(done), s.slots,
                            ctx.traffic["check_requests"], seed32(ctx.seed, 2))
    params = s.ref.make_params(ctx.config, s.wseed)
    widest = widest_control = 0.0
    bad = s.failed
    for i in pick:
        prompt, served = done[i]
        served = np.asarray(served)
        if served.shape != (s.max_new,) or served.min() < 0 \
                or served.max() >= s.vocab:
            bad += 1
            continue
        g = s.ref.served_gaps(ctx.config, params, prompt, served, control)
        widest = max(widest, float(np.max(g["gaps"])))
        if control:
            widest_control = max(widest_control,
                                 float(np.max(g["control_gaps"])))
    out = {"served_logit_gap": {"value": widest,
                                "limit": ctx.traffic["gap_limit"]},
           "requests_malformed": {"value": bad, "limit": 0},
           "requests_compared": {"value": len(pick), "limit": "> 0"}}
    if control:
        out["control_logit_gap"] = {"value": widest_control,
                                    "limit": ctx.traffic["gap_limit"]}
    return out
