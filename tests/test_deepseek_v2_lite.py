"""DeepSeek-V2-Lite's family on the CPU at a small size, in float32, against
the plain reference the benchmark uses (``bench/configs/deepseek-v2-lite.py``,
loaded by path: one reference, not two).

Latent attention's two forms (and, beside them, GQA's), YaRN at the
published settings, the dropless expert layer, one chip's share of the
experts against the uncut layer, the serving path through ``serve.Engine``
with its counters, and ``common.attention`` with values narrower than the
queries."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Runtime, get_model, gqa, mla
from repro.models.common import attention, yarn_rope
from repro.models.moe import moe_ffn
from repro.serve import Engine, ServeConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness.common import load_json, load_module  # noqa: E402

REF = load_module("configs", "deepseek-v2-lite.py")
KIND = load_module("kinds", "serve_latent.py")
PUBLISHED = load_json("configs", "deepseek-v2-lite.json")


def small(**over) -> dict:
    """The configuration file at the rehearsal's widths, in float32."""
    c = {**PUBLISHED, **PUBLISHED["rehearsal"], "torch_dtype": "float32"}
    return {**c, **over}


def program(c: dict):
    return get_model(KIND._program_config(c, rehearse=False))


# Float32 on both sides: what is left is summation order and the absorbed
# decode against the reference's expanded form, about 1e-6 of a logit of
# size ~1; a routing decision taken differently would move a logit by
# 1e-2 or more.
TOL = dict(rtol=1e-4, atol=1e-4)


def test_prefill_then_decode_matches_reference():
    c = small()
    model = program(c)
    params = REF.make_params(c, seed=7, dtype=jnp.float32)
    rng = np.random.default_rng(7)
    b, s, steps = 2, 12, 8
    toks = rng.integers(0, c["vocab_size"], (b, s + steps)).astype(np.int32)
    rt = Runtime(q_chunk=0)
    got = []
    logits, cache = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, s + steps, rt))(
            params, toks[:, :s])
    got.append(logits)
    decode = jax.jit(lambda p, t, cc: model.decode_step(p, {"tokens": t}, cc,
                                                        rt),
                     donate_argnums=(2,))
    for j in range(steps):
        logits, cache = decode(params, toks[:, s + j:s + j + 1], cache)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], axis=1)   # (B, steps+1, V)
    for i in range(b):
        want = REF.logits(c, params, toks[i], s - 1)
        np.testing.assert_allclose(got[i], np.asarray(want)[:steps + 1],
                                   **TOL)
    assert int(cache["len"]) == s + steps


@pytest.mark.parametrize("arch,module", [
    ("deepseek-v2-lite", mla), ("internlm2-1.8b", gqa), ("qwen3-14b", gqa)])
def test_absorbed_decode_equals_expanded(arch, module):
    """Each attention module's two forms agree: a token decoded against
    the cache of the positions before it (``attend_cached``) reads what
    the whole-sequence form (``attend``) gives at its position, and
    returns the cache entries that form made there.  qwen3-14b adds the
    query/key norms."""
    cfg = get_config(arch).smoke().replace(dtype="float32")
    params, _ = get_model(cfg).init(jax.random.PRNGKey(3))
    p = jax.tree.map(lambda a: a[0], params["layers"])
    b, t, first = 2, 10, 4
    h = jax.random.normal(jax.random.PRNGKey(4), (b, t, cfg.d_model))
    out, rows = module.attend(cfg, p, h)
    cache = {k: jnp.zeros((b, t + 3) + v.shape[2:]).at[:, :t].set(v)
             for k, v in rows.items()}
    for pos in range(first, t):
        o, new = module.attend_cached(cfg, p, h[:, pos:pos + 1], cache, pos)
        np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(out[:, pos]),
                                   rtol=1e-5, atol=1e-5)
        for k, v in rows.items():
            np.testing.assert_allclose(np.asarray(new[k][:, 0]),
                                       np.asarray(v[:, pos]), rtol=1e-6,
                                       atol=1e-6)


def test_yarn_at_published_settings():
    cfg = get_config("deepseek-v2-lite")
    y = yarn_rope(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    assert (y["low"], y["high"]) == (10, 23)
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    mask = 1 - np.clip((i - 10) / 13, 0, 1)
    want = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(y["inv_freq"], want, rtol=1e-6)
    assert y["cos_scale"] == 1.0
    assert mla.softmax_scale(cfg) == pytest.approx(0.114721, abs=5e-7)
    ref = REF.yarn(PUBLISHED)
    assert (ref["low"], ref["high"]) == (10, 23)
    np.testing.assert_allclose(y["inv_freq"], ref["inv_freq"], rtol=1e-6)
    assert ref["softmax_scale"] == pytest.approx(mla.softmax_scale(cfg),
                                                 rel=1e-12)


def test_dropless_when_every_token_routes_to_one_expert():
    """All router scores equal, top-1: every token goes to expert 0.  A
    capacity would drop most of them in the 16-token prefill and none in a
    2-token decode step; dropless, prefill and decode agree with the
    full forward pass."""
    cfg = get_config("deepseek-v2-lite").smoke().replace(dtype="float32",
                                                         moe_top_k=1)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(5))
    moe = params["layers"]["moe"]
    moe["router"] = jnp.zeros_like(moe["router"])
    b, s = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(6), (b, s + 2), 0,
                              cfg.vocab_size)
    rt = Runtime(q_chunk=0)
    full, _ = model.forward(params, {"tokens": toks}, rt)
    logits, cache = model.prefill(params, {"tokens": toks[:, :s]}, s + 2, rt)
    np.testing.assert_allclose(logits, full[:, s - 1], rtol=1e-5, atol=1e-5)
    for j in range(2):
        logits, cache = model.decode_step(
            params, {"tokens": toks[:, s + j:s + j + 1]}, cache, rt)
        np.testing.assert_allclose(logits, full[:, s + j], rtol=1e-5,
                                   atol=1e-5)
    expert_layers = cfg.n_layers - cfg.dense_layers
    pairs = b * (s + 2) * expert_layers
    assert int(cache["counters"]["moe_pairs"]) == pairs
    # every expert held and k = 1: the t*k rows handed to the grouped
    # matmuls are the pairs themselves
    assert int(cache["counters"]["moe_rows"]) == pairs


def test_shares_add_up_to_the_uncut_layer():
    """Eight chips, each holding an eighth of the routed experts, routing
    over all of them: their outputs, with the shared experts counted once,
    add up to the reference's whole layer."""
    c = small(n_routed_experts=16,
              deployment={"routed_experts": 16, "held": [0, 16]})
    params = REF.make_params(c, seed=9, dtype=jnp.float32)
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    cfg = KIND._program_config(c, rehearse=False)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 16, cfg.d_model))
    shared = REF._swiglu(x, p["shared"]["w1"], p["shared"]["w3"],
                         p["shared"]["w2"])
    total = shared
    pairs = 0
    rows = x.shape[0] * x.shape[1] * cfg.moe_top_k
    for chip in range(8):
        lo, n = 2 * chip, 2
        share = {**p, **{k: p[k][lo:lo + n] for k in ("w1", "w3", "w2")}}
        y, _, counts = moe_ffn(share, x, cfg.replace(moe_held=n,
                                                      moe_held_first=lo))
        total = total + (y - shared)
        pairs += int(counts["moe_pairs"])
        # the grouped matmuls are handed every pair row, held or not
        assert int(counts["moe_rows"]) == rows
        assert int(counts["moe_rows"]) >= int(counts["moe_pairs"])
    s = REF.sizes(c)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda xb: REF.experts(xb, p, s))(x)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    assert pairs == x.shape[0] * x.shape[1] * cfg.moe_top_k


def test_smoke_and_param_count():
    full = get_config("deepseek-v2-lite")
    assert full.param_count() == 15_706_484_224
    assert full.param_count(active_only=True) == 2_661_150_208
    share = full.replace(moe_held=8)
    assert share.param_count() == 3_110_989_312
    shapes = get_model(share).param_shapes()
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 3_110_989_312
    smoke = full.smoke()
    assert smoke.family == "mla" and smoke.n_layers > smoke.dense_layers
    params, _ = get_model(smoke).init(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == smoke.param_count()


def test_engine_reads_the_counters_once_a_wave(tmp_path):
    from test_tracing import _named, _spans

    cfg = get_config("deepseek-v2-lite").smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(11))
    eng = Engine(model, params, ServeConfig(max_len=16, slots=2))
    prompts = np.arange(16, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    eng.generate_batch(prompts, max_new=4)               # compiles
    with jax.profiler.trace(str(tmp_path)):
        eng.generate_batch(prompts, max_new=4)
    spans = _spans(tmp_path)
    read, = _named(spans, "mt4g.serve.counters")
    assert all(s[2] <= read[1] for s in _named(spans, "mt4g.serve.decode"))
    # two waves: each token of the prompts and of every decode step (the
    # last one's logits unsampled) through every expert layer, top-k pairs
    tokens = 2 * (8 + 4) * (cfg.n_layers - cfg.dense_layers)
    assert eng.counters == {"moe_pairs": 2 * tokens * cfg.moe_top_k,
                            "moe_rows": 2 * tokens * cfg.moe_top_k}


@pytest.mark.parametrize("q_chunk", [0, 4])
def test_attention_with_narrower_values(q_chunk):
    b, t, hq, hkv, hd, vd = 2, 8, 4, 2, 12, 7
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(ks[0], (b, t, hq, hd))
    k = jax.random.normal(ks[1], (b, t, hkv, hd))
    v = jax.random.normal(ks[2], (b, t, hkv, vd))
    out = attention(q, k, v, causal=True, q_chunk=q_chunk, scale=0.3)
    assert out.shape == (b, t, hq, vd)
    kk = np.repeat(np.asarray(k), hq // hkv, axis=2)
    vv = np.repeat(np.asarray(v), hq // hkv, axis=2)
    scores = np.einsum("bsnh,btnh->bnst", np.asarray(q), kk) * 0.3
    scores = np.where(np.tri(t, dtype=bool), scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bnst,btnh->bsnh", probs, vv)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
