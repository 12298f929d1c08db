#!/usr/bin/env python3
"""Read a cell's compared numbers from the program and from its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, one run of the cell as ``bench/run.py`` makes it (the
program: the lower reading of each limit) and one with the cell's control
in place (``CONTROL`` of the traffic kind: the upper reading), all in this
one process.  A serving cell reads both in one run: the control is the
float8 reference, read at the positions the program served.  Prints one
JSON line per run and a summary line; ``--rehearse 1`` runs on the CPU at
tiny sizes, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import common  # noqa: E402


def readings(workload: str, seed: int, seconds: float, rehearse: int,
             faults=None) -> dict:
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0", "--rehearse", str(rehearse)]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run.main(argv, faults=faults)
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)
    spec = common.benchmark_spec()
    cell, _ = common.find_cell(spec, args.workload)
    kind_name = common.load_json("traffic", f"{cell['traffic']}.json")["kind"]
    kind = common.load_module("kinds", f"{kind_name}.py")
    both_in_one = kind_name == "serve"
    summary = {"program": [], "control": []}
    for seed in (int(s) for s in args.seeds.split(",")):
        sides = [("control", kind.CONTROL)] if both_in_one else \
            [("program", None), ("control", kind.CONTROL)]
        for side, faults in sides:
            res = readings(args.workload, seed, args.seconds, args.rehearse,
                           faults)
            line = {"side": side, "seed": seed, "correct": res["correct"],
                    "checks": res["checks"], "metrics": res["metrics"]}
            print(json.dumps(line), flush=True)
            if both_in_one:
                summary["program"].append(
                    res["checks"]["served_logit_gap"]["value"])
                summary["control"].append(
                    res["checks"]["control_logit_gap"]["value"])
            else:
                summary[side].append(res["correct"])
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
