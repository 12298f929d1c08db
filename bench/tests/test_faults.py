"""Each cell rehearsed on the CPU: sound as the program runs, and
``correct`` false with the timed path broken underneath, once for each
fault the cell can have.  The look for a chip is the one step skipped."""
import json

import numpy as np
import pytest

import run


def rehearse(capsys, workload, seed, seconds, faults=None) -> dict:
    run.main(["--workload", workload, "--seed", str(seed), "--seconds",
              str(seconds), "--rehearse", "1"], faults=faults)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------------------ discovery
def _chase_answer_altered(name, args, kw, out):
    if name == "pchase_kernel_batch":
        return out.at[:, 1].add(1)
    return out


def _half_the_blocks_left_out(name, args, kw, out):
    if name == "stream_write_kernel":
        return out.at[out.shape[0] // 2:].set(0)
    if name == "stream_read_kernel":
        return out.at[out.shape[0] // 2:].set(0)
    return out


def _persisted_altered(store):
    put = store.put

    def altered(key, topo, meta=None):
        topo = type(topo).from_json(topo.to_json())
        dm = topo.find_memory("DeviceMemory")
        dm.set("read_bw", dm.get("read_bw") + 0.1, "GB/s")
        return put(key, topo, meta)

    store.put = altered


@pytest.mark.parametrize("faults, number", [
    (None, None),
    ({"kernel": _chase_answer_altered}, "chase_mismatch"),
    ({"kernel": _half_the_blocks_left_out}, "stream_mismatch"),
    ({"store_put": _persisted_altered}, "persisted_mismatch"),
])
def test_rediscover(capsys, faults, number):
    res = rehearse(capsys, "v5e-node.rediscover", 2 ** 33 + 5, 1.0, faults)
    assert res["checks"]["kernel_outputs_compared"]["value"] > 0
    if number is None:
        assert res["correct"], res["checks"]
    else:
        assert not res["correct"]
        assert res["checks"][number]["value"] > 0, res["checks"]


# -------------------------------------------------------------- serving
def _token_altered(engine):
    """Step 2's token of every sequence is replaced by its neighbour."""
    sample, generate = engine._sample, engine.generate_batch
    vocab = engine.model.cfg.vocab_size
    step = {"n": 0}

    def altered(logits, rng):
        out = sample(logits, rng)
        step["n"] += 1
        return (out + 1) % vocab if step["n"] == 2 else out

    def batch(*a, **kw):
        step["n"] = 0
        return generate(*a, **kw)

    engine._sample, engine.generate_batch = altered, batch


def _half_the_batch_left_out(engine):
    """Only the first half of each wave is served; the rest get its
    answers."""
    generate = engine.generate_batch

    def half(prompts, max_new, eos_id=None, seed=0):
        h = max(prompts.shape[0] // 2, 1)
        out = generate(prompts[:h], max_new, eos_id, seed)
        return np.concatenate([out, out], axis=0)[: prompts.shape[0]]

    engine.generate_batch = half


@pytest.mark.parametrize("workload", ["internlm2-1.8b.chat",
                                      "internlm2-1.8b.rag"])
@pytest.mark.parametrize("fault", [None, _token_altered,
                                   _half_the_batch_left_out])
def test_serving(capsys, workload, fault):
    faults = {"engine": fault} if fault else None
    res = rehearse(capsys, workload, 2 ** 33 + 7, 0.5, faults)
    if fault is None:
        assert res["correct"], res["checks"]
    else:
        assert not res["correct"], res["checks"]
        assert res["checks"]["served_logit_gap"]["value"] > \
            res["checks"]["served_logit_gap"]["limit"]
