"""Traffic kind ``serve_latent``: the closed queue of ``serve`` for a
latent-attention expert model (DeepSeek-V2's family) through
``repro.serve.Engine``.

The parameters, the waves, the compared requests and the check are those
of ``kinds/serve.py``, loaded by path; this kind maps the configuration's
own keys (latent attention, the experts held here, YaRN) onto the
program's config and runs the prefill the file's ``program`` names.  It
resolves the program's config before drawing any weight, so a program that
lacks the family stops within seconds.

End-to-end: ``tokens_per_s``, as ``serve``.  Counters: ``model_flops``
(``harness.arith_latent``, the routed experts from the pairs the program
counted), and the expert layer's ``moe_pairs`` and ``moe_rows`` over the
window, read once a wave by the engine.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from harness.arith_latent import Latent
from harness.common import load_module, seed32

serve = load_module("kinds", "serve.py")
CONTROL = serve.CONTROL
check = serve.check

# What the program implements of the published options; anything else is
# refused rather than served under the wrong equations.
PUBLISHED = {"q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "moe_layer_freq": 1, "n_group": 1,
             "topk_group": 1, "hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False,
             "routed_scaling_factor": 1}


def _program_config(c: dict, rehearse: bool):
    """The program's model config, at the widths the file states."""
    from repro.configs import get_config

    base = get_config(c["program"]["arch"])
    from repro.configs.base import Yarn

    for k, v in PUBLISHED.items():
        if c[k] != v:
            raise SystemExit(f"bench: {k}={c[k]!r} is not implemented "
                             f"(the program takes {v!r})")
    rs, dep = c["rope_scaling"], c["deployment"]
    cfg = base.replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        dense_layers=c["first_k_dense_replace"],
        kv_lora_rank=c["kv_lora_rank"], qk_nope_dim=c["qk_nope_head_dim"],
        qk_rope_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        moe_experts=dep["routed_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        moe_shared_d_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
        moe_held=c["n_routed_experts"], moe_held_first=dep["held"][0],
        moe_norm_topk=c["norm_topk_prob"],
        yarn=Yarn(factor=float(rs["factor"]),
                  original_max_pos=rs["original_max_position_embeddings"],
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])))
    if rehearse:
        cfg = cfg.replace(name=cfg.name + "-rehearsal")
    return cfg


class LatentServing(serve.Serving):
    """``serve.Serving`` with this family's config and prefill."""

    def __init__(self, ctx, cfg):
        import jax

        from repro.models import Runtime, get_model
        from repro.serve import Engine, ServeConfig

        t = ctx.traffic
        self.ctx = ctx
        self.ref = load_module("configs", f"{ctx.config_name}.py")
        self.slots, self.plen = t["slots"], t["prompt_len"]
        self.max_new, self.max_len = t["max_new"], t["max_len"]
        self.vocab = ctx.config["vocab_size"]
        self.wseed = seed32(ctx.seed, 1)
        model = get_model(cfg)
        want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
        params = self.ref.make_params(ctx.config, self.wseed)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise SystemExit("bench: the program's parameter tree differs "
                             "from the reference's layout")
        rt = Runtime(q_chunk=ctx.config["program"]["q_chunk"])
        self.engine = Engine(model, params, ServeConfig(
            max_len=self.max_len, slots=self.slots, rt=rt))
        self._spans()
        if ctx.faults.get("engine"):
            ctx.faults["engine"](self.engine)
        self.rng = np.random.default_rng(ctx.seed32)
        self.done: list[tuple[np.ndarray, np.ndarray]] = []
        self.failed = 0


def setup(ctx):
    if ctx.rehearse:
        ctx.config = {**ctx.config, **ctx.config["rehearsal"]}
    cfg = _program_config(ctx.config, ctx.rehearse)
    s = LatentServing(ctx, cfg)
    ctx.mark("weights")
    s.serve_wave(s.wave_prompts(), 2)       # compiles prefill and decode
    ctx.mark("warm-up")
    return s


def window(ctx, s: LatentServing) -> dict:
    before = dict(s.engine.counters)
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
    counted = {k: v - before.get(k, 0) for k, v in s.engine.counters.items()}
    tokens = sum(int(np.asarray(o).size) for _, o in s.done)
    m = Latent.from_config(ctx.config)
    flops = waves * (m.prefill_flops(s.slots, s.plen) + sum(
        m.decode_flops(s.slots, s.plen + j, 0) for j in range(s.max_new)))
    flops += m.routed_flops(counted.get("moe_pairs", 0))
    return {"attempted": waves * s.slots + s.failed, "failed": s.failed,
            "wall_s": wall,
            "end_to_end": {"tokens_per_s": tokens / wall},
            "counters": {"waves": waves, "slots": s.slots,
                         "prompt_len": s.plen, "max_new": s.max_new,
                         "generated_tokens": tokens,
                         "decode_steps": waves * s.max_new,
                         "model_flops": flops, **counted}}
