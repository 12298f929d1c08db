"""Each cell's control, rehearsed on the CPU at tiny sizes, comes out as
not correct, where the program on the same seeds comes out correct.

On the chip the same controls ran at the cells' own sizes
(``bench/control.py``); the readings and the limits set from them are in
PERF.md.  Here a serving cell compares with the limit its traffic file
gives for the rehearsal sizes.
"""
import pytest

import control

SEEDS = (7, 8, 9)


@pytest.mark.parametrize("workload", ["v5e-node.rediscover"])
def test_control_fails_where_the_program_passes(workload):
    from harness import common

    spec = common.benchmark_spec()
    cell, _ = common.find_cell(spec, workload)
    kind = common.load_json("traffic", f"{cell['traffic']}.json")["kind"]
    ctl = common.load_module("kinds", f"{kind}.py").CONTROL
    seed = SEEDS[0]
    assert control.readings(workload, seed, 2.0, 1)["correct"]
    res = control.readings(workload, seed, 2.0, 1, ctl)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", ["internlm2-1.8b.chat",
                                      "internlm2-1.8b.rag"])
def test_float8_control_reads_above_the_limit(workload):
    for seed in SEEDS:
        res = control.readings(workload, seed, 0.5, 1, {"fp8_control": True})
        c = res["checks"]
        assert c["served_logit_gap"]["value"] <= \
            c["served_logit_gap"]["limit"], c    # the program passes
        assert c["control_logit_gap"]["value"] > \
            c["control_logit_gap"]["limit"], c
        assert not res["correct"]
