"""The ``deepseek-v2-lite.chat_decode`` cell rehearsed on the CPU: sound
as the program runs, and ``correct`` false with the served path broken
underneath: a served token altered, half of each wave left out, and the
held experts' part dropped from the program's expert layers."""
import json

import jax.numpy as jnp
import pytest

import run
from test_faults import _half_the_batch_left_out, _token_altered

CELL = "deepseek-v2-lite.chat_decode"


def rehearse(capsys, seed, faults=None) -> dict:
    run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
              "--rehearse", "1"], faults=faults)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _held_experts_dropped(engine):
    """Every expert layer computes its shared experts alone: the held
    routed experts' down projections are zero in the program's weights."""
    moe = engine.params["layers"]["moe"]
    moe["w2"] = jnp.zeros_like(moe["w2"])


@pytest.mark.parametrize("fault", [None, _token_altered,
                                   _half_the_batch_left_out,
                                   _held_experts_dropped])
def test_serve_latent(capsys, fault):
    res = rehearse(capsys, 2 ** 33 + 11, {"engine": fault} if fault else None)
    gap = res["checks"]["served_logit_gap"]
    if fault is None:
        assert res["correct"], res["checks"]
    else:
        assert not res["correct"], res["checks"]
        assert gap["value"] > gap["limit"]
