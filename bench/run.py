#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration, its
traffic mix and its per-layer metrics are files of their own under
``bench/``, and the traffic mix names the generator (``bench/kinds``) that
drives it.  Set-up (imports, data and weights from the seed, compiles,
warm-up of the cell's own shapes) is timed as ``setup_s``; then the window
runs for ``--seconds``, traced by JAX's profiler with ``--trace 1``; then
``correct`` is decided by comparing what the window produced with the
plain reference beside the configuration.  The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number beside its limit (also the last lines of stderr).

Without a TPU, with fewer chips than the cell asks for, or with a device
kind missing from ``bench/peaks.json`` it exits non-zero and prints no
result.  ``--rehearse 1`` instead runs the cell on the CPU at tiny sizes
(``JAX_PLATFORMS=cpu``, Pallas interpreted) and prints no metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402
from types import SimpleNamespace                          # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_context(args, faults=None) -> SimpleNamespace:
    """Everything the cell's files say, found by the cell's name."""
    spec = common.benchmark_spec()
    cell, cfg = common.find_cell(spec, args.workload)
    traffic = common.load_json("traffic", f"{cell['traffic']}.json")
    with open(os.path.join(common.ROOT, cfg["file"])) as f:
        config = json.load(f)
    if args.rehearse:
        traffic = {**traffic, **traffic.get("rehearsal", {})}

    def span(name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    marks = [("start", T_START)]

    def mark(name):
        """End of a phase of set-up, reported on stderr."""
        marks.append((name, time.perf_counter()))

    return SimpleNamespace(
        spec=spec, cell=cell, config_name=cfg["name"], config=config,
        traffic=traffic, seed=args.seed, seed32=common.seed32(args.seed),
        seconds=args.seconds, trace=bool(args.trace),
        rehearse=bool(args.rehearse), faults=faults or {}, span=span,
        mark=mark, marks=marks)


def wanted(spec: dict, cell: str, kind: str, reported=()) -> list[dict]:
    """The metrics of ``kind`` (``end_to_end``/``per_layer``) that this
    cell reports: those listing it, or listing no cells (per-layer: those
    whose ``moves`` metric the cell reports)."""
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def main(argv=None, faults=None) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    ctx = cell_context(args, faults)
    import jax

    import repro  # noqa: F401 — the system under test, beside bench/

    from harness.trace import breakdown, flatten, reduce
    ctx.mark("imports")

    if not args.rehearse:
        common.enable_compile_cache()
    log = common.CompileLog()
    if args.rehearse:
        from harness.rehearsal import REHEARSAL_DEVICE
        ctx.device = dict(REHEARSAL_DEVICE)
    else:
        ctx.device = common.check_device(ctx.cell["chips"])
    ctx.mark("device")
    kind = common.load_module("kinds", f"{ctx.traffic['kind']}.py")

    state = None
    trace_dir = None
    try:
        state = kind.setup(ctx)
        setup_s = time.perf_counter() - T_START
        compiled_before = log.count
        if ctx.trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        tracing = (jax.profiler.trace(trace_dir) if ctx.trace
                   else contextlib.nullcontext())
        with tracing:
            with ctx.span("bench.window"):
                out = kind.window(ctx, state)
        compiles = log.count - compiled_before
        phases = ", ".join(f"{b[0]} {b[1] - a[1]:.3f} s"
                           for a, b in zip(ctx.marks, ctx.marks[1:]))
        print(f"bench: set-up {setup_s:.3f} s ({phases}); {compiles} "
              f"compiles inside the window", file=sys.stderr, flush=True)
        memory_peak = common.peak_memory_bytes(ctx.cell["chips"])
        checks = kind.check(ctx, state)
        red = reduce(flatten(trace_dir)) if ctx.trace else None
    finally:
        if state is not None and hasattr(kind, "close"):
            kind.close(ctx, state)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {k: ctx.device[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": common.checks_pass(checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": {}, "device": device}
    name = ctx.cell["name"]
    e2e = {"setup_s": setup_s, **out["end_to_end"]}
    reported = [m["name"] for m in wanted(ctx.spec, name, "end_to_end")]
    if args.rehearse:
        result["rehearsal"] = True
    elif not ctx.trace:
        for m in wanted(ctx.spec, name, "end_to_end"):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        if red is None:
            raise SystemExit("bench: the trace holds no window or device")
        device["busy_s"] = red["busy_ns"] * 1e-9
        device["window_s"] = red["window_ns"] * 1e-9
        record = {"counters": out["counters"], "trace": red,
                  "window_s": out["wall_s"], "peaks": ctx.device["peaks"],
                  "config": ctx.config, "traffic": ctx.traffic,
                  "end_to_end": e2e}
        for m in wanted(ctx.spec, name, "per_layer", reported):
            reader = common.load_module("metrics", f"{m['name']}.py")
            value = reader.read(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = breakdown(red)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
