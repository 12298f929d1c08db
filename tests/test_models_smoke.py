"""Per-architecture smoke tests (assignment requirement): a REDUCED config of
each family runs one real forward/train step on CPU — output shapes + no NaNs
— plus decode/prefill consistency for the serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Full-model jit compiles (one per arch): minutes of XLA time.
# Deselected by `make test-fast`.
pytestmark = pytest.mark.slow

from repro.configs import ARCHS, get_config, shape_for
from repro.models import Runtime, get_model

ALL_ARCHS = sorted(ARCHS)


def _smoke_batch(cfg, rng, b=2, s=16):
    r = np.random.default_rng(rng)
    if cfg.family == "audio":
        toks = r.integers(0, cfg.vocab_size, (b, cfg.n_codebooks, s))
        return {"tokens": jnp.asarray(toks, jnp.int32),
                "targets": jnp.asarray(toks, jnp.int32)}
    if cfg.family == "vlm":
        text = s
        toks = r.integers(0, cfg.vocab_size, (b, text))
        patches = r.normal(size=(b, cfg.n_patches, cfg.vision_embed_dim))
        return {"patches": jnp.asarray(patches, jnp.bfloat16),
                "tokens": jnp.asarray(toks, jnp.int32),
                "targets": jnp.asarray(toks, jnp.int32)}
    toks = r.integers(0, cfg.vocab_size, (b, s))
    return {"tokens": jnp.asarray(toks, jnp.int32),
            "targets": jnp.asarray(toks, jnp.int32)}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_loss(arch):
    cfg = get_config(arch).smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple))
    batch = _smoke_batch(cfg, rng=0)
    loss = jax.jit(lambda p, b: model.train_loss(p, b))(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss is not finite"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_grads_finite(arch):
    cfg = get_config(arch).smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(1))
    batch = _smoke_batch(cfg, rng=1)
    grads = jax.jit(jax.grad(lambda p: model.train_loss(p, batch)))(params)
    leaves = jax.tree.leaves(grads)
    assert leaves
    assert all(np.all(np.isfinite(np.asarray(l, np.float32))) for l in leaves)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_prefill_decode_consistency(arch):
    """prefill(t tokens) then decode_step must equal forward(t+1 tokens) on
    the next-token logits — the KV-cache/state correctness contract."""
    cfg = get_config(arch).smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(2))
    b, s = 2, 8
    batch = _smoke_batch(cfg, rng=2, b=b, s=s + 1)

    if cfg.family == "audio":
        full = batch["tokens"]
        pre = {"tokens": full[:, :, :s]}
        nxt = {"tokens": full[:, :, s:s + 1]}
        whole = {"tokens": full}
    else:
        full = batch["tokens"]
        pre = {k: v for k, v in batch.items() if k != "targets"}
        pre = dict(pre)
        pre["tokens"] = full[:, :s]
        nxt = {"tokens": full[:, s:s + 1]}
        whole = {k: v for k, v in batch.items() if k != "targets"}

    rt = Runtime(q_chunk=0)
    max_len = s + 4 + (cfg.n_patches if cfg.family == "vlm" else 0)
    logits_pre, cache = jax.jit(
        lambda p, bb: model.prefill(p, bb, max_len=max_len, rt=rt))(params, pre)
    logits_dec, cache = jax.jit(
        lambda p, bb, c: model.decode_step(p, bb, c, rt=rt))(params, nxt, cache)
    logits_full, _ = jax.jit(lambda p, bb: model.forward(p, bb, rt=rt))(
        params, whole)

    if cfg.family == "audio":
        want_last = logits_full[:, s - 1]      # logits at position s-1...
        got = logits_pre
        want_next = logits_full[:, s]
    else:
        want_last = logits_full[:, s - 1]
        got = logits_pre
        want_next = logits_full[:, s]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want_last, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(logits_dec, np.float32),
                               np.asarray(want_next, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_multistep_decode_consistency(arch):
    """prefill, then 4 decode steps in a row, each through a jit that donates
    the cache: every step's logits equal forward's at its position, so the
    write position moves and the earlier tokens stay in the cache."""
    cfg = get_config(arch).smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(4))
    b, s, steps = 2, 8, 4
    batch = _smoke_batch(cfg, rng=4, b=b, s=s + steps)
    whole = {k: v for k, v in batch.items() if k != "targets"}
    full = batch["tokens"]              # (B, T) or audio's (B, K, T)

    def tokens(lo, hi):
        return {"tokens": full[..., lo:hi]}

    rt = Runtime(q_chunk=0)
    max_len = s + steps + (cfg.n_patches if cfg.family == "vlm" else 0)
    _, cache = jax.jit(
        lambda p, bb: model.prefill(p, bb, max_len=max_len, rt=rt))(
            params, {**whole, **tokens(0, s)})
    decode = jax.jit(lambda p, bb, c: model.decode_step(p, bb, c, rt=rt),
                     donate_argnums=(2,))
    logits_full, _ = jax.jit(lambda p, bb: model.forward(p, bb, rt=rt))(
        params, whole)
    for j in range(steps):
        logits, cache = decode(params, tokens(s + j, s + j + 1), cache)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(logits_full[:, s + j], np.float32),
            rtol=2e-2, atol=2e-2, err_msg=f"{arch}: decode step {j}")


def test_loss_decreases_tiny_overfit():
    """A few SGD steps on one batch must reduce the loss (dense family)."""
    cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    batch = _smoke_batch(cfg, rng=3)

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(lambda q: model.train_loss(q, batch))(p)
        return l, jax.tree.map(lambda w, gw: w - 0.5 * gw, p, g)

    losses = []
    for _ in range(8):
        l, params = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.9, losses
