"""Train loop / optimizer / data / checkpoint / FT / serve / compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Train-loop/checkpoint/serve integration: many jit compiles.
# Deselected by `make test-fast`.
pytestmark = pytest.mark.slow

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.data import ByteCorpus, DataConfig, SyntheticLM
from repro.ft import (FailureInjector, RestartExhausted, StragglerDetector,
                      Supervisor)
from repro.models import get_model
from repro.serve import Engine, ServeConfig
from repro.train import (OptConfig, TrainConfig, compress_with_feedback,
                         dequantize, init_train_state, lr_at, make_train_step,
                         quantize, train_loop)


def _tiny_setup(microbatches=1, steps_total=64):
    cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
    model = get_model(cfg)
    tc = TrainConfig(
        opt=OptConfig(lr=3e-3, warmup_steps=4, total_steps=steps_total,
                      master_f32=True),
        microbatches=microbatches, ckpt_every=4)
    data = ByteCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                 global_batch=8))
    return cfg, model, tc, data


class TestOptimizer:
    def test_lr_schedule(self):
        oc = OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                       min_lr_ratio=0.1)
        assert float(lr_at(oc, jnp.int32(0))) == 0.0
        assert float(lr_at(oc, jnp.int32(10))) == pytest.approx(1.0, rel=1e-5)
        assert float(lr_at(oc, jnp.int32(100))) == pytest.approx(0.1, rel=1e-4)

    def test_training_reduces_loss(self):
        cfg, model, tc, data = _tiny_setup()
        state, _ = init_train_state(model, jax.random.PRNGKey(0), tc)
        state, hist = train_loop(model, tc, data, steps=30, state=state)
        first = np.mean([m["loss"] for _, m in hist[:3]])
        last = np.mean([m["loss"] for _, m in hist[-3:]])
        assert last < first * 0.8, (first, last)

    def test_grad_accum_equivalence(self):
        """microbatches=4 must match microbatches=1 numerically (f32)."""
        cfg, model, tc1, data = _tiny_setup(microbatches=1)
        tc4 = TrainConfig(opt=tc1.opt, microbatches=4)
        s1, _ = init_train_state(model, jax.random.PRNGKey(1), tc1)
        s4 = jax.tree.map(lambda x: x, s1)
        batch = data.batch_at(0)
        s1, m1 = jax.jit(make_train_step(model, tc1))(s1, batch)
        s4, m4 = jax.jit(make_train_step(model, tc4))(s4, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s4["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestData:
    def test_determinism_and_restartability(self):
        d = SyntheticLM(DataConfig(vocab_size=100, seq_len=16, global_batch=4))
        b1, b2 = d.batch_at(7), d.batch_at(7)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(d.batch_at(8)["tokens"], b1["tokens"])

    def test_host_sharding_disjoint(self):
        mk = lambda h: SyntheticLM(DataConfig(vocab_size=1000, seq_len=8,
                                              global_batch=8, n_hosts=2,
                                              host_id=h))
        a, b = mk(0).batch_at(3), mk(1).batch_at(3)
        assert a["tokens"].shape == (4, 8)
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_targets_are_shifted_tokens(self):
        d = ByteCorpus(DataConfig(vocab_size=256, seq_len=16, global_batch=2))
        b = d.batch_at(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["targets"][:, :-1])


class TestCheckpoint:
    def test_roundtrip_bitwise(self, tmp_path):
        cfg, model, tc, data = _tiny_setup()
        state, _ = init_train_state(model, jax.random.PRNGKey(2), tc)
        ck = Checkpointer(str(tmp_path))
        ck.save(5, state, extra={"note": "hi"})
        restored, extra = ck.restore(state, step=5)
        assert extra["note"] == "hi"
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_save_and_gc(self, tmp_path):
        ck = Checkpointer(str(tmp_path), keep=2)
        tree = {"w": jnp.arange(10.0)}
        for s in (1, 2, 3, 4):
            ck.save_async(s, tree)
        ck.wait()
        assert ck.steps() == [3, 4]          # keep=2

    def test_resume_bitwise_equals_uninterrupted(self, tmp_path):
        """Checkpoint/restart at step 4 must reproduce the 8-step run exactly
        (deterministic pipeline + pure step)."""
        cfg, model, tc, data = _tiny_setup()
        s0, _ = init_train_state(model, jax.random.PRNGKey(3), tc)
        step_fn = jax.jit(make_train_step(model, tc))

        # Uninterrupted 8 steps.
        sa = jax.tree.map(lambda x: x, s0)
        sa, _ = train_loop(model, tc, data, steps=8, state=sa,
                           step_fn=step_fn)

        # 4 steps -> checkpoint -> restore -> 4 more.
        ck = Checkpointer(str(tmp_path))
        sb = jax.tree.map(lambda x: x, s0)
        sb, _ = train_loop(model, tc, data, steps=4, state=sb,
                           step_fn=step_fn)
        ck.save(4, sb)
        sb_restored, _ = ck.restore(sb, step=4)
        sb2, _ = train_loop(model, tc, data, steps=8, state=sb_restored,
                            start_step=4, step_fn=step_fn)
        for a, b in zip(jax.tree.leaves(sa["params"]),
                        jax.tree.leaves(sb2["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFaultTolerance:
    def test_supervisor_restarts_and_completes(self, tmp_path):
        cfg, model, tc, data = _tiny_setup()
        ck = Checkpointer(str(tmp_path))
        s0, _ = init_train_state(model, jax.random.PRNGKey(4), tc)
        step_fn = jax.jit(make_train_step(model, tc))
        injector = FailureInjector(fail_at={6})

        def train_fn(state, start):
            return train_loop(model, tc, data, steps=10, state=state,
                              start_step=start, checkpointer=ck,
                              step_fn=step_fn, callbacks=[injector])

        sup = Supervisor(ck, max_restarts=2)
        state, hist = sup.run(train_fn, s0)
        assert sup.restarts == 1
        assert any("restart from step" in l for l in sup.log)
        assert hist[-1][0] == 9              # completed all steps

    def test_supervisor_gives_up(self, tmp_path):
        ck = Checkpointer(str(tmp_path))

        def bad_fn(state, start):
            raise RuntimeError("always broken")

        sup = Supervisor(ck, max_restarts=2)
        with pytest.raises(RestartExhausted):
            sup.run(bad_fn, {"x": jnp.zeros(1)})

    def test_straggler_detector(self):
        det = StragglerDetector(threshold_sigmas=4.0)
        for i in range(20):
            assert not det.record(i, 1.0 + 0.01 * (i % 3))
        assert det.record(20, 5.0)           # 5x median -> flagged
        assert det.flagged and det.flagged[0][0] == 20


class TestCompression:
    def test_quantize_roundtrip_error_bound(self):
        x = np.random.default_rng(0).normal(size=(256,)).astype(np.float32)
        q, s = quantize(jnp.asarray(x), bits=8)
        err = np.abs(np.asarray(dequantize(q, s)) - x)
        assert err.max() <= float(s) * 0.5 + 1e-7

    def test_error_feedback_reduces_bias(self):
        """Accumulated error feedback keeps the long-run mean unbiased."""
        rng = np.random.default_rng(1)
        true_sum = np.zeros(64, np.float32)
        fed_sum = np.zeros(64, np.float32)
        err = jnp.zeros(64, jnp.float32)
        for _ in range(200):
            g = rng.normal(size=64).astype(np.float32) * 1e-3
            true_sum += g
            q, s, err = compress_with_feedback(jnp.asarray(g), err, bits=8)
            fed_sum += np.asarray(dequantize(q, s))
        resid = np.abs(fed_sum + np.asarray(err) - true_sum).max()
        assert resid < 1e-4

    def test_compressed_psum_single_device(self):
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
        g = jnp.linspace(-1, 1, 32)
        e = jnp.zeros(32)
        fn = jax.jit(jax.shard_map(
            lambda gg, ee: __import__("repro.train.grad_compress",
                                      fromlist=["compressed_psum"]
                                      ).compressed_psum(gg, ee, "d"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())))
        out, err = fn(g, e)
        np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=1e-2)


def _serial_generation(eng, prompts, max_new, eos_id=None, seed=0):
    """The serial token loop, as a reference: fetch each step's logits,
    select on the host (``np.argmax``, or a draw at the engine's
    temperature), then decode."""
    rng = np.random.default_rng(seed)
    logits, cache = eng._prefill(
        eng.params, {"tokens": jnp.asarray(prompts, jnp.int32)})
    outs, alive = [], np.ones(prompts.shape[0], bool)
    for _ in range(max_new):
        host = np.asarray(logits, np.float32)
        temp = eng.cfg.temperature
        if temp <= 0:
            nxt = np.argmax(host, axis=-1)
        else:
            z = host / temp
            p = np.exp(z - z.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            nxt = np.array([rng.choice(p.shape[-1], p=row) for row in p])
        outs.append(nxt)
        if eos_id is not None:
            alive &= nxt != eos_id
            if not alive.any():
                break
        logits, cache = eng._decode(
            eng.params, {"tokens": jnp.asarray(nxt[:, None], jnp.int32)},
            cache)
    return np.stack(outs, axis=1)


def _smoke_engine(arch, key, **serve):
    cfg = get_config(arch).smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(key))
    return Engine(model, params, ServeConfig(**serve)), cfg


class TestServe:
    @pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v2-lite"])
    def test_greedy_generation_matches_serial_loop(self, arch):
        """Selecting on the device and reading one step behind serves the
        tokens the serial host loop serves."""
        eng, cfg = _smoke_engine(arch, 12, max_len=32, slots=3)
        prompts = (np.arange(18, dtype=np.int32).reshape(3, 6) * 7) \
            % cfg.vocab_size
        got = eng.generate_batch(prompts, max_new=6)
        np.testing.assert_array_equal(
            got, _serial_generation(eng, prompts, 6))
        assert got.shape == (3, 6) and eng.steps == 6

    def test_sampling_at_a_temperature_matches_serial_loop(self):
        eng, cfg = _smoke_engine("internlm2-1.8b", 13, max_len=32, slots=2,
                                 temperature=0.7)
        prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab_size
        got = eng.generate_batch(prompts, max_new=5, seed=3)
        np.testing.assert_array_equal(
            got, _serial_generation(eng, prompts, 5, seed=3))

    def test_greedy_tokens_stay_on_the_device(self):
        """Every token batch the decode program is handed is the device
        array ``_sample`` returned; ``_sample`` runs once per token."""
        eng, cfg = _smoke_engine("internlm2-1.8b", 14, max_len=32, slots=2)
        sampled, handed = [], []
        sample, decode = eng._sample, eng._decode

        def spy_sample(logits, rng):
            assert isinstance(logits, jax.Array)
            sampled.append(sample(logits, rng))
            return sampled[-1]

        def spy_decode(p, b, c):
            handed.append(b["tokens"])
            return decode(p, b, c)

        eng._sample, eng._decode = spy_sample, spy_decode
        prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab_size
        eng.generate_batch(prompts, max_new=5)
        assert len(sampled) == 5 and len(handed) == 5
        assert all(isinstance(t, jax.Array) for t in handed)
        assert all(t is s for t, s in zip(handed, sampled))

    def test_eos_cuts_where_the_serial_loop_cuts(self):
        """One slot emits the stop id at step 1 and the other at step 4:
        the batch stops after step 4, as the serial loop does, although
        the device ran a step ahead."""
        eng, cfg = _smoke_engine("internlm2-1.8b", 1, max_len=32, slots=2)
        prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab_size
        full = _serial_generation(eng, prompts, 8)
        eos = full[0, 1]
        assert eos not in full[0, :1] and eos not in full[1, :4]
        assert full[1, 4] == eos
        want = _serial_generation(eng, prompts, 8, eos_id=eos)
        got = eng.generate_batch(prompts, max_new=8, eos_id=eos)
        assert want.shape == (2, 5)
        np.testing.assert_array_equal(got, want)

    def test_greedy_generation_deterministic(self):
        cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(5))
        eng = Engine(model, params, ServeConfig(max_len=32, slots=2))
        prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab_size
        a = eng.generate_batch(prompts, max_new=5)
        b = eng.generate_batch(prompts, max_new=5)
        np.testing.assert_array_equal(a, b)
        assert a.shape == (2, 5)

    def test_generation_matches_stepwise_forward(self):
        """Engine output == greedy argmax of repeated full forwards."""
        cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(6))
        eng = Engine(model, params, ServeConfig(max_len=32))
        prompts = (np.arange(8, dtype=np.int32)[None] * 3) % cfg.vocab_size
        gen = eng.generate_batch(prompts, max_new=4)

        toks = prompts.copy()
        from repro.models import Runtime
        fwd = jax.jit(lambda p, b: model.forward(p, b, Runtime(q_chunk=0)))
        for i in range(4):
            logits, _ = fwd(params, {"tokens": jnp.asarray(toks, jnp.int32)})
            nxt = np.argmax(np.asarray(logits, np.float32)[:, -1], -1)
            assert nxt[0] == gen[0, i], f"mismatch at step {i}"
            toks = np.concatenate([toks, nxt[:, None].astype(np.int32)], 1)

    def test_decode_step_donates_its_cache(self):
        """The decode program writes into the cache it is given: the whole
        cache aliases the output, and each cache a step is handed is gone
        after it."""
        cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(8))
        eng = Engine(model, params, ServeConfig(max_len=32, slots=2))
        cache = model.init_cache(2, 32)
        tokens = {"tokens": jnp.zeros((2, 1), jnp.int32)}
        memory = eng._decode.lower(params, tokens, cache).compile() \
            .memory_analysis()
        kv_bytes = cache["k"].nbytes + cache["v"].nbytes
        assert memory.alias_size_in_bytes == kv_bytes + cache["len"].nbytes

        handed, decode = [], eng._decode

        def spy(p, b, c):
            handed.append(c)
            return decode(p, b, c)

        eng._decode = spy
        prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab_size
        eng.generate_batch(prompts, max_new=3)
        assert len(handed) == 3
        assert all(c["k"].is_deleted() and c["v"].is_deleted() for c in handed)

    def test_continuous_batching_queue(self):
        cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
        model = get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(7))
        eng = Engine(model, params, ServeConfig(max_len=32, slots=2))
        reqs = [np.full(4, i, np.int32) for i in range(5)]
        outs = eng.serve(reqs, max_new=3)
        assert len(outs) == 5 and all(o.shape == (3,) for o in outs)
