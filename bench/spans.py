#!/usr/bin/env python3
"""Trace one cell's window and print what the program's own spans show.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        [--out <dir>] [--rehearse 1]

The cell is set up as ``bench/run.py`` sets it up; then its window runs
once under ``jax.profiler.trace`` and once untraced, each ``--seconds``
long.  The traced window is reduced twice: by ``harness.trace`` (the
benchmark's per-layer metrics and breakdown, as ``run.py --trace 1``
reports them) and by ``harness.program`` (the ``mt4g.`` spans: per-span
totals, the host-device clock offset, the share of idle time the spans
cover, and the numbers they give per discovery or per decode step).  The
last line of stdout is one JSON object; with ``--out`` the flattened trace
is written there first (``<cell>.<seed>.flat.json.gz``, device events by
their stable names, which is all either reduction reads of them).  Nothing
is compared with a reference: ``run.py`` decides ``correct``.

The cost of tracing is the traced window's discoveries or waves per second
against the untraced window's.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

import run
from harness import common, program
from harness.trace import breakdown, reduce, stable_name

# The idle time each kind's spans should cover: inside each discovery;
# in serving, the gaps the breakdown labels as after a decode call.
SCOPE = {"rediscover": "bench.discovery", "serve": "after bench.decode"}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _rate(out: dict) -> float:
    """Discoveries or waves completed per second of the window."""
    c = out["counters"]
    n = c["waves"] if "waves" in c else out["attempted"]
    return n / out["wall_s"]


def _dump(flat: dict, path: str) -> None:
    for p in flat["planes"]:
        if p["name"].startswith("/device:"):
            for line in p["lines"]:
                for e in line["events"]:
                    e[0] = stable_name(e[0])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(flat, f)


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    ctx = run.cell_context(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=1, rehearse=args.rehearse))
    import jax

    if args.rehearse:
        from harness.rehearsal import REHEARSAL_DEVICE
        ctx.device = dict(REHEARSAL_DEVICE)
    else:
        common.enable_compile_cache()
        ctx.device = common.check_device(ctx.cell["chips"])
    name, kind_name = ctx.cell["name"], ctx.traffic["kind"]
    kind = common.load_module("kinds", f"{kind_name}.py")
    state = kind.setup(ctx)
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        with jax.profiler.trace(trace_dir):
            with ctx.span("bench.window"):
                traced = kind.window(ctx, state)
        t0 = time.perf_counter()
        flat = program.flatten(trace_dir)
        if args.out:
            _dump(flat, os.path.join(args.out,
                                     f"{name}.{args.seed}.flat.json.gz"))
        red = reduce(flat)
        prog = program.program(flat)
        cov = program.coverage(flat, prog, SCOPE[kind_name])
        reduce_s = time.perf_counter() - t0
        plain = kind.window(ctx, state)
    finally:
        if hasattr(kind, "close"):
            kind.close(ctx, state)
        shutil.rmtree(trace_dir, ignore_errors=True)

    result = {"workload": name, "seed": args.seed,
              "rate": {"traced": _rate(traced), "untraced": _rate(plain)},
              "end_to_end": traced["end_to_end"],
              "counters": traced["counters"], "reduce_s": reduce_s}
    if red is not None:
        record = {"counters": traced["counters"], "trace": red,
                  "window_s": traced["wall_s"], "peaks": ctx.device["peaks"],
                  "config": ctx.config, "traffic": ctx.traffic,
                  "end_to_end": traced["end_to_end"]}
        reported = [m["name"] for m in run.wanted(ctx.spec, name,
                                                  "end_to_end")]
        result["per_layer"] = {
            m["name"]: common.load_module("metrics", f"{m['name']}.py")
            .read(record)
            for m in run.wanted(ctx.spec, name, "per_layer", reported)}
        result["breakdown"] = breakdown(red)
        result["modules"] = {k: v["count"] for k, v in red["modules"].items()}
    result["program"] = program.metrics(prog, traced["counters"])
    if prog is not None:
        result["clock"] = prog["clock"]
        result["spans"] = prog["spans"]
        result["idle_ns"] = prog["idle_ns"]
    result["coverage"] = cov
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
