"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (assignment deliverable (d)).

  table1_coverage    — paper Table I:   attribute coverage of discovery
  table3_validation  — paper Table III: discovered vs ground truth
  fig2_reduction     — paper Fig. 2:    eq.2 reduction + K-S change point
  runtime_breakdown  — paper §V-A:      per-family probe run times
  fig5_stream        — paper Fig. 5:    stream ns/B vs size, LLC boundary
  perfmodel          — paper §VI-A:     CWP/MWP verdicts from discovery
  roofline           — deliverable (g): per-cell terms from dry-run artifacts
  kernels            — Pallas kernels vs refs (correctness + ref wall time)
  train_step         — tiny end-to-end train step wall time
  topology_query     — cold discovery vs warm store hit vs batched queries
  topology_http      — live HTTP front end: concurrent batched qps +
                       p50/p99 request latency (correctness hard-gated)
  remote_discovery   — remote write path: sim jobs submitted over HTTP,
                       retry survival + idempotent store hit hard-gated
  adaptive_speedup   — probe rows: adaptive sweep planner vs dense sweeps
                       (discrete attributes must be identical)
  pallas_interp      — third-backend discovery through the real Pallas
                       kernels (interpret mode) vs configured ground truth

CLI (the CI bench-regression gate consumes the machine-readable form):

  --json             emit rows as a JSON array on stdout instead of CSV
  --out FILE         also write the JSON rows to FILE
  --only a,b,c       run only the named benchmarks (function-name suffixes)
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

ROWS: list[tuple[str, float, str]] = []
JSON_MODE = False


def row(name: str, us: float, derived: str) -> None:
    ROWS.append((name, us, derived))
    if not JSON_MODE:
        print(f"{name},{us:.1f},{derived}", flush=True)


def rows_as_json() -> list[dict]:
    return [{"name": n, "us": round(u, 1), "derived": d} for n, u, d in ROWS]


def _timed(fn, *args, repeats=3, **kw):
    fn(*args, **kw)
    best = np.inf
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter_ns()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter_ns() - t0)
    return out, best / 1e3


# ----------------------------------------------------------- paper tables
def bench_table1_coverage() -> None:
    """Attribute coverage on the simulated H100 (paper Table I)."""
    from repro.core import discover_sim, make_h100_like

    t0 = time.perf_counter_ns()
    topo, _ = discover_sim(make_h100_like(seed=42), n_samples=17)
    us = (time.perf_counter_ns() - t0) / 1e3
    covered = total = 0
    for me in topo.memory:
        for attr in ("size", "load_latency", "line_size", "fetch_granularity",
                     "amount"):
            if me.kind == "cache" or attr in ("size", "load_latency"):
                total += 1
                covered += me.get(attr) is not None
    row("table1_coverage", us, f"{covered}/{total}_attrs")


def bench_table3_validation() -> None:
    """Discovered values vs simulated ground truth (paper Table III)."""
    from repro.core import discover_sim, make_h100_like, make_mi210_like

    for make, name in ((make_h100_like, "h100"), (make_mi210_like, "mi210")):
        dev = make(seed=43)
        t0 = time.perf_counter_ns()
        topo, _ = discover_sim(dev, n_samples=17)
        us = (time.perf_counter_ns() - t0) / 1e3
        gt = dev.ground_truth()
        ok = bad = 0
        for lvl, truth in gt.items():
            me = topo.find_memory(lvl)
            if me is None:
                continue
            for attr, want in truth.items():
                if attr in ("physical_group", "scope"):
                    continue
                got = me.get(attr if attr != "latency" else "load_latency")
                if got is None:
                    continue
                tol = 0.1 if attr in ("size", "latency") else 0.0
                good = (abs(got - want) <= tol * want) if tol else got == want
                ok += bool(good)
                bad += not good
        row(f"table3_validation_{name}", us, f"{ok}ok_{bad}bad")


def bench_fig2_reduction() -> None:
    """eq.2 reduction + K-S change point on a size sweep (paper Fig. 2)."""
    from repro.core import make_h100_like
    from repro.core.probes import SimRunner, find_size

    runner = SimRunner(make_h100_like(seed=44))
    res, us = _timed(find_size, runner, "L1", repeats=1, n_samples=17)
    row("fig2_reduction", us,
        f"size={res.size}B_conf={res.confidence:.2f}_pts={res.reduced.size}")


def bench_runtime_breakdown() -> None:
    """Per-family probe run times (paper §V-A)."""
    from repro.core import discover_sim, make_h100_like

    _, timings = discover_sim(make_h100_like(seed=45), n_samples=17)
    for fam, secs in sorted(timings.per_family.items()):
        row(f"runtime_{fam}", secs * 1e6, f"{secs/timings.total:.1%}_of_total")


def bench_engine_speedup() -> None:
    """Engine vs legacy discovery wall time (the engine's headline row —
    since ISSUE 4, the engine side runs the adaptive sweep planner, so the
    gate floor moved from 2x to 3x).  Summed over the two validation
    devices; topologies are checked equivalent first — a speedup over
    different answers would be meaningless.  'Identical' means the
    ROADMAP-prescribed contract: discrete attributes exactly equal, floats
    within rel-tol, confidence excluded (the planner computes it from a
    boundary window instead of the full sweep series)."""
    from repro.core import (SweepBudget, discover_sim, discover_sim_legacy,
                            make_h100_like, make_mi210_like,
                            topology_equivalent)

    legacy_s = engine_s = 0.0
    identical = True
    for make in (make_h100_like, make_mi210_like):
        legacy_best = engine_best = np.inf
        # Best-of-5, interleaved: this box is a 2-core shared VM with heavy
        # steal time, and a single steal burst inside a ~200 ms engine run
        # would otherwise dominate the ratio.
        for _ in range(5):
            t0 = time.perf_counter()
            topo_l, _ = discover_sim_legacy(make(seed=48), n_samples=17)
            legacy_best = min(legacy_best, time.perf_counter() - t0)
            t0 = time.perf_counter()
            topo_e, _ = discover_sim(make(seed=48), n_samples=17,
                                     budget=SweepBudget())
            engine_best = min(engine_best, time.perf_counter() - t0)
        legacy_s += legacy_best
        engine_s += engine_best
        if not topology_equivalent(topo_l, topo_e, rel_tol=1e-6,
                                   compare_confidence=False):
            identical = False
    row("engine_speedup", engine_s * 1e6,
        f"legacy={legacy_s*1e6:.0f}us_speedup={legacy_s/engine_s:.2f}x_"
        f"identical={identical}")


def bench_adaptive_speedup() -> None:
    """ISSUE 4 tentpole row: probe volume of the adaptive planner vs the
    dense sweeps, same devices, same seeds.  ``identical`` (hard-gated) is
    the planner-vs-dense oracle contract — every discrete attribute equal,
    floats within rel-tol, confidence excluded; ``row_ratio`` (ratio-gated)
    is rows_dense / rows_planned, the probe-volume cut every backend
    inherits."""
    from repro.core import (SweepBudget, discover_sim, make_h100_like,
                            make_mi210_like, topology_equivalent)

    rows_dense = rows_planned = 0
    identical = True
    t0 = time.perf_counter()
    for make in (make_h100_like, make_mi210_like):
        topo_d, td = discover_sim(make(seed=48), n_samples=17)
        topo_p, tp = discover_sim(make(seed=48), n_samples=17,
                                  budget=SweepBudget())
        rows_dense += td.probe_rows
        rows_planned += tp.probe_rows
        if not topology_equivalent(topo_d, topo_p, rel_tol=1e-6,
                                   compare_confidence=False):
            identical = False
    us = (time.perf_counter() - t0) * 1e6
    row("adaptive_speedup", us,
        f"rows_dense={rows_dense}_rows_planned={rows_planned}_"
        f"row_ratio={rows_dense/rows_planned:.2f}x_identical={identical}")


def bench_parallel_speedup() -> None:
    """ISSUE 10 tentpole row: multiprocess sharding of batched probe calls.

    One large fused-style ``pchase_many`` batch (512 rows x 2001 samples)
    run inline and through a dedicated worker-process pool with
    shared-memory sample transport.  ``identical`` (hard-gated) is the
    whole correctness claim — request-keyed sampling makes row placement
    invisible, so the pooled matrix must equal the inline one byte for
    byte.  ``speedup`` is warn-only: it measures the CI box's core count
    (a 1-2 core container *loses* to inline; the >=1.8x acceptance number
    needs >=4 real cores), not the sharding design.
    """
    from repro.core import make_h100_like
    from repro.core.engine.parallel import (ParallelConfig, ParallelPool,
                                            effective_cpu_count,
                                            maybe_parallel_runner)
    from repro.core.probes import SimRunner

    reqs = [("L2", 256 * 1024 + 4096 * i, 64) for i in range(512)]
    n_samples = 2001
    inline = SimRunner(make_h100_like(seed=50))
    inline.pchase_many(reqs[:8], n_samples)        # touch code paths once
    t0 = time.perf_counter()
    want = np.asarray(inline.pchase_many(reqs, n_samples))
    inline_s = time.perf_counter() - t0

    workers = max(2, min(4, effective_cpu_count()))
    cfg = ParallelConfig(workers=workers)
    with ParallelPool(cfg) as pool:
        pooled = maybe_parallel_runner(SimRunner(make_h100_like(seed=50)),
                                       cfg, pool=pool)
        pooled.pchase_many(reqs[:workers], 5)      # warm: spawn + rebuild
        t0 = time.perf_counter()
        got = np.asarray(pooled.pchase_many(reqs, n_samples))
        pooled_s = time.perf_counter() - t0
    identical = bool(np.array_equal(want, got))
    row("parallel_speedup", pooled_s * 1e6,
        f"inline={inline_s*1e6:.0f}us_speedup={inline_s/pooled_s:.2f}x_"
        f"workers={workers}_rows={len(reqs)}_identical={identical}")


def bench_pallas_interp() -> None:
    """Third-backend row (ISSUE 3 tentpole): full discovery through the
    real Pallas probe kernels in interpret mode, via the same engine path
    as the sim backend.  Correctness fields (hard-gated): the discovered
    discrete attributes must match the configured ground truth (cache
    spaces exact, <=64 B sweep-grid quantization on the word-granular
    scratchpad), and a second store-backed discovery must be a pure hit
    returning the identical document.  Wall time is warn-only — interpret
    mode characterizes this container, not a TPU.

    One retry on a discrete mismatch: probes here are *real timed
    measurements* on a shared box, and a sustained steal burst can defeat
    even the drift-hardened detection (a few-percent tail).  A genuine
    regression fails deterministically on both attempts; independent
    drift flukes square away.  Retries are reported in the derived field.
    """
    import tempfile

    from repro.core import discover_pallas
    from repro.core.engine.store import TopologyStore
    from repro.core.probes import PallasRunner, make_pallas_model

    def attempt():
        with tempfile.TemporaryDirectory() as td:
            store = TopologyStore(td)
            model = make_pallas_model()
            runner = PallasRunner(model, interpret=True)
            t0 = time.perf_counter()
            topo, _ = discover_pallas(runner=runner, interpret=True,
                                      n_samples=9, store=store)
            cold_s = time.perf_counter() - t0

            gt = model.ground_truth()
            ok = True
            for name in ("L1", "L2"):
                me = topo.find_memory(name)
                ok = ok and me is not None \
                    and me.get("size") == gt[name]["size"] \
                    and me.get("line_size") == gt[name]["line_size"] \
                    and me.get("fetch_granularity") == gt[name][
                        "fetch_granularity"]
            vmem = topo.find_memory("VMEM")
            ok = ok and vmem is not None and vmem.get("size") is not None \
                and abs(vmem.get("size") - gt["VMEM"]["size"]) <= 64

            calls = runner.kernel_calls
            # §IV-F/G/H rows coalesced onto shared eviction grids: more
            # rows than dispatches means the fusion actually batched them.
            fused = (runner.eviction_grid_calls > 0
                     and runner.eviction_grid_rows
                     > runner.eviction_grid_calls)
            t0 = time.perf_counter()
            topo_hit, _ = discover_pallas(runner=runner, interpret=True,
                                          n_samples=9, store=store)
            hit_s = max(time.perf_counter() - t0, 1e-9)
            served = (topo_hit.to_json() == topo.to_json()
                      and runner.kernel_calls == calls)
            return bool(ok), bool(served), bool(fused), cold_s, hit_s, calls

    ok, served, fused, cold_s, hit_s, calls = attempt()
    retried = False
    if not (ok and served):
        retried = True
        ok, served, fused, cold_s, hit_s, calls = attempt()
    row("pallas_interp", cold_s * 1e6,
        f"discrete_ok={ok}_store_hit={served}_eviction_fusion={fused}_"
        f"warm_speedup={cold_s/hit_s:.1f}x_kernel_calls={calls}_"
        f"retried={retried}")


def bench_fig5_stream() -> None:
    """Stream ns/B vs array size on the host; detect the cache boundary
    (paper Fig. 5). The transition on a shared VM is gradual, so the
    parametric PELT segmentation (one of the paper's 'other algorithms')
    locates the mean shift on the short series."""
    import jax
    import jax.numpy as jnp
    from repro.core.stats import pelt_segments

    sizes = [1 << s for s in range(19, 27)]        # 512 KiB .. 64 MiB
    ns_per_b = []
    t0 = time.perf_counter_ns()
    for n in sizes:
        x = jnp.arange(n // 4, dtype=jnp.float32)
        f = jax.jit(jnp.sum)
        f(x).block_until_ready()                   # warm-up
        reps = max(3, (1 << 24) // n)
        t1 = time.perf_counter_ns()
        for _ in range(reps):
            f(x).block_until_ready()
        dt = (time.perf_counter_ns() - t1) / reps
        ns_per_b.append(dt / n)
    us = (time.perf_counter_ns() - t0) / 1e3
    cps = pelt_segments(np.asarray(ns_per_b))
    boundary = sizes[cps[0] - 1] if cps else -1
    row("fig5_stream", us, f"cache_boundary={boundary}B_ncps={len(cps)}")


def bench_perfmodel() -> None:
    """CWP/MWP verdicts with MT4G-discovered parameters (paper §VI-A)."""
    from repro.core import discover_sim, make_h100_like
    from repro.core.perfmodel import (AppParams, evaluate,
                                      gpu_params_from_topology)

    topo, _ = discover_sim(make_h100_like(seed=46), n_samples=9)
    gpu = gpu_params_from_topology(topo)
    stream_app = AppParams(comp_cycles=20, mem_cycles=4000, loads_per_warp=32,
                           active_warps_per_sm=48)
    gemm_app = AppParams(comp_cycles=8000, mem_cycles=400, loads_per_warp=2,
                         active_warps_per_sm=48)
    r1, us = _timed(evaluate, stream_app, gpu, repeats=3)
    r2 = evaluate(gemm_app, gpu)
    row("perfmodel", us,
        f"stream_membound={r1.memory_bound}_gemm_membound={r2.memory_bound}")


def bench_link_adjacency() -> None:
    """Pod-level §IV-H analogue: recover a 4x8 torus's direct ICI links."""
    from repro.core.probes.adjacency import SimPod, find_link_adjacency

    pod = SimPod(rows=4, cols=8, seed=47)
    res, us = _timed(find_link_adjacency, pod, repeats=1, n_samples=9)
    correct = sum(res.neighbors[c] == pod.neighbors(c)
                  for c in range(pod.n_chips))
    row("link_adjacency", us,
        f"{correct}/{pod.n_chips}_chips_exact_thr={res.threshold_us:.2f}us")


def bench_topology_query() -> None:
    """The serving story: cold discovery vs warm store hit vs batched query
    throughput over the topology service (ISSUE 2 tentpole headline: a warm
    hit must be >=10x faster than cold discovery — re-serving a stored
    topology is a pure read, not a re-measurement)."""
    import tempfile

    from repro.core import discover_sim, make_h100_like, make_mi210_like
    from repro.core.engine.store import TopologyStore
    from repro.serve.topology_service import TopologyService

    with tempfile.TemporaryDirectory() as td:
        store = TopologyStore(td)
        t0 = time.perf_counter()
        topo_cold, _ = discover_sim(make_h100_like(seed=49), n_samples=17,
                                    store=store)
        cold_s = time.perf_counter() - t0
        warm_s = np.inf
        for _ in range(5):
            t0 = time.perf_counter()
            topo_warm, _ = discover_sim(make_h100_like(seed=49), n_samples=17,
                                        store=store)
            warm_s = min(warm_s, time.perf_counter() - t0)
        identical = topo_cold.to_json() == topo_warm.to_json()

        discover_sim(make_mi210_like(seed=49), n_samples=17, store=store)
        svc = TopologyService(store, hot_set=8)
        paths = ("L1.size", "L2.load_latency", "hbm.bandwidth",
                 "DeviceMemory.read_bw", "L2.segment_size")
        reqs = [(k, p) for k in store.keys() for p in paths] * 200
        svc.query_batch(reqs[:10])       # warm the hot set
        t0 = time.perf_counter()
        answers = svc.query_batch(reqs)
        q_s = time.perf_counter() - t0
        found = sum(a.found for a in answers)
        row("topology_query", warm_s * 1e6,
            f"cold={cold_s*1e6:.0f}us_warm_speedup={cold_s/warm_s:.1f}x_"
            f"batched_qps={len(reqs)/q_s:.0f}_found={found}/{len(reqs)}_"
            f"identical={identical}")


def bench_topology_http() -> None:
    """ISSUE 6 tentpole row: the HTTP front end under concurrent batched
    traffic.  Correctness fields (hard-gated): every lookup found, zero
    transport/5xx errors (``ok``).  Throughput (``batched_qps``) and the
    per-request latency percentiles are warn-only at first — they
    characterize the CI box's loopback + GIL, not the serving design."""
    import tempfile
    import threading

    from repro.core import discover_sim, make_h100_like, make_mi210_like
    from repro.core.engine.store import TopologyStore
    from repro.serve import TopologyClient, TopologyHTTPServer

    with tempfile.TemporaryDirectory() as td:
        store = TopologyStore(td)
        discover_sim(make_h100_like(seed=49), n_samples=9, store=store)
        discover_sim(make_mi210_like(seed=49), n_samples=9, store=store)

        paths = ("L1.size", "L2.load_latency", "hbm.bandwidth",
                 "DeviceMemory.read_bw", "general.clock_domain")
        with TopologyHTTPServer(store) as server:
            keys = store.keys()
            batch = [(k, p) for k in keys for p in paths] * 10   # 100 pairs
            n_threads, n_reqs = 4, 10
            latencies: list[list[float]] = [[] for _ in range(n_threads)]
            found = [0] * n_threads
            errors = [0] * n_threads

            def worker(tid: int) -> None:
                client = TopologyClient(server.url)
                for _ in range(n_reqs):
                    t0 = time.perf_counter()
                    try:
                        results = client.query_batch(batch)
                        found[tid] += sum(r["found"] for r in results)
                    except Exception:   # noqa: BLE001 — counted, gated
                        errors[tid] += 1
                    latencies[tid].append(time.perf_counter() - t0)

            TopologyClient(server.url).query_batch(batch[:10])   # warm
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall_s = time.perf_counter() - t0

        lat_us = np.sort(np.concatenate(latencies)) * 1e6
        total = len(batch) * n_threads * n_reqs
        total_found = sum(found)
        total_errors = sum(errors)
        ok = total_found == total and total_errors == 0
        row("topology_http", wall_s * 1e6,
            f"batched_qps={total/wall_s:.0f}_"
            f"p50={np.percentile(lat_us, 50):.0f}us_"
            f"p99={np.percentile(lat_us, 99):.0f}us_"
            f"found={total_found}/{total}_errors={total_errors}_ok={ok}")


def bench_remote_discovery() -> None:
    """ISSUE 7 tentpole row: the remote discovery write path end to end.

    Submits three sim-backed discovery jobs over a live authenticated
    server (one with an injected transient runner fault that must be
    retried to success), then resubmits one request to prove idempotency
    (store hit, zero runner probes) and compares the remotely-discovered
    topology against a direct ``discover_sim`` of the same request.
    Correctness fields are hard-gated (``completed``, ``retried_ok``,
    ``idem_ok``, ``correct``, ``ok``); the submit->done wall time is
    warn-only — it measures loopback HTTP + the CI box, not the design.
    """
    import tempfile

    from repro.core import discover_sim
    from repro.core.engine.store import TopologyStore
    from repro.core.simulate import SIM_DEVICES
    from repro.serve import TopologyClient, TopologyHTTPServer
    from repro.serve.jobs import JobEngine, TransientRunnerError

    requests = [{"backend": "sim", "device": d, "seed": 7, "n_samples": 9}
                for d in ("h100", "mi210", "v5e")]
    faulted = {"left": 1}

    def inject(job, attempt):
        # exactly one transient fault, on the first attempt the pool makes
        if faulted["left"] > 0 and attempt == 0:
            faulted["left"] -= 1
            raise TransientRunnerError("injected bench fault")

    with tempfile.TemporaryDirectory() as td:
        store = TopologyStore(os.path.join(td, "store"))
        engine = JobEngine(store, workers=2, backoff_base_s=0.01,
                           on_attempt=inject)
        with TopologyHTTPServer(store, auth_token="bench-token",
                                job_engine=engine, job_poll_s=0) as server:
            client = TopologyClient(server.url, auth_token="bench-token",
                                    max_retries=2)
            t0 = time.perf_counter()
            jobs = [client.submit_discovery(r) for r in requests]
            finals = [client.wait(j["job_id"], timeout_s=120, poll_s=0.05)
                      for j in jobs]
            wall_s = time.perf_counter() - t0

            completed = sum(f["state"] == "done" for f in finals)
            # one job ate the injected fault and recovered on attempt 2
            retried_ok = (faulted["left"] == 0
                          and sorted(f["attempts"] for f in finals)
                          == [1, 1, 2]
                          and all(f["result"]["store_hit"] is False
                                  for f in finals))
            # idempotency: resubmitting a completed request is a pure
            # store hit — zero runner probes
            again = client.wait(
                client.submit_discovery(requests[0])["job_id"],
                timeout_s=120, poll_s=0.05)
            idem_ok = (again["state"] == "done"
                       and again["key"] == finals[0]["key"]
                       and again["result"]["store_hit"] is True)

        # the remotely-written topology equals a direct discovery of the
        # same request (modulo free-text notes, which embed wall times)
        direct_store = TopologyStore(os.path.join(td, "direct"))
        discover_sim(SIM_DEVICES["sim-h100"](seed=7), n_samples=9,
                     store=direct_store)

        def doc(s, key):
            return {k: v for k, v in s.get(key).topology.to_json().items()
                    if k != "notes"}

        key = finals[0]["key"]
        correct = (direct_store.keys() == [key]
                   and doc(direct_store, key) == doc(store, key))

    ok = completed == 3 and retried_ok and idem_ok and correct
    row("remote_discovery", wall_s * 1e6,
        f"completed={completed}/3_retried_ok={retried_ok}_"
        f"idem_ok={idem_ok}_correct={correct}_ok={ok}")


def bench_fault_recovery() -> None:
    """ISSUE 9 tentpole row: discovery reliability under injected faults.

    Four legs against one h100 sim device, all hard-gated except the
    overhead ratio's exact value:

    * ``equivalent`` — a discovery under a value-preserving transient
      fault schedule (every fault retried by the engine) is
      ``topology_equivalent`` to the clean run;
    * ``degraded_ok`` — a permanently-failing family lands as an
      ``"unknown"`` attribute with ``degraded`` provenance instead of
      aborting the run;
    * ``resume_ok`` — a discovery killed mid-run leaves a checkpoint, and
      the rerun resumes from it re-probing ZERO persisted rows (exact
      sample-cache miss arithmetic) before producing the equivalent
      topology and clearing the spent checkpoint;
    * ``retry_overhead`` — faulted/clean wall-time ratio, gated against a
      ceiling: retries must cost bounded re-dispatches, not a rerun.
    """
    import tempfile

    from repro.core import make_h100_like
    from repro.core.discover import (DiscoveryRequest, discover,
                                     discover_sim, sim_request_descriptor)
    from repro.core.engine.store import TopologyStore, request_key
    from repro.core.errors import Resilience
    from repro.core.probes import ChaosRunner, FaultSchedule, SimRunner
    from repro.core.topology import PROVENANCE_DEGRADED, topology_equivalent

    n = 9
    families = ("sharing", "device_memory_latency",
                "device_memory_bandwidth")
    policy = Resilience(max_retries=3, sleep=lambda _s: None)

    def request(make_runner, resilience=policy):
        dev = make_h100_like(seed=3)
        return DiscoveryRequest(
            descriptor=sim_request_descriptor(dev, n, None,
                                              resilience=resilience),
            vendor=dev.vendor, model=dev.name,
            backend=f"simulated:{dev.name}",
            make_runner=make_runner, n_samples=n,
            device_families=families, resilience=resilience)

    # leg 1: clean vs transient-faulted equivalence (+ overhead ratio)
    t0 = time.perf_counter()
    clean_topo, clean_t = discover_sim(make_h100_like(seed=3), n_samples=n)
    clean_s = time.perf_counter() - t0
    chaos = {}

    def mk_flaky():
        chaos["r"] = ChaosRunner(
            SimRunner(make_h100_like(seed=3)),
            FaultSchedule(seed=11, transient_rate=0.05,
                          max_faults_per_request=1))
        return chaos["r"]

    t0 = time.perf_counter()
    faulted_topo, faulted_t = discover(request(mk_flaky))
    faulted_s = time.perf_counter() - t0
    equivalent = (chaos["r"].faults_injected > 0
                  and faulted_t.meta["resilience"]["retries"] > 0
                  and faulted_t.meta["resilience"]["degraded"] == []
                  and topology_equivalent(clean_topo, faulted_topo,
                                          rel_tol=1e-6))
    retry_overhead = faulted_s / clean_s

    # leg 2: permanent fault degrades the family, never aborts the run
    topo, t = discover(request(
        lambda: ChaosRunner(SimRunner(make_h100_like(seed=3)),
                            FaultSchedule(seed=7,
                                          permanent_kinds=("bandwidth",)))))
    attr = topo.find_memory("L2").attrs.get("read_bw")
    degraded_ok = ("L2/bandwidth" in t.meta["resilience"]["degraded"]
                   and attr is not None and attr.value == "unknown"
                   and attr.provenance == PROVENANCE_DEGRADED)

    # leg 3: kill mid-run, resume from the checkpoint with zero recompute
    with tempfile.TemporaryDirectory() as td:
        store = TopologyStore(os.path.join(td, "store"))
        try:
            discover(request(
                lambda: ChaosRunner(SimRunner(make_h100_like(seed=3)),
                                    FaultSchedule(seed=5, kill_after=40))),
                store=store)
            resume_ok = False            # the kill never fired: no resume
        except RuntimeError:
            key = request_key(request(
                lambda: SimRunner(make_h100_like(seed=3))).descriptor)
            ckpt = store.load_checkpoint(key)
            resumed, rt = discover(request(
                lambda: SimRunner(make_h100_like(seed=3))), store=store)
            resume_ok = (
                ckpt is not None
                and rt.meta["resume"]["rows"] == len(ckpt[0])
                and rt.meta["cache"]["misses"] + len(ckpt[0])
                == clean_t.meta["cache"]["misses"]
                and topology_equivalent(clean_topo, resumed, rel_tol=1e-6)
                and not store.has_checkpoint(key))

    ok = equivalent and degraded_ok and resume_ok
    row("fault_recovery", faulted_s * 1e6,
        f"equivalent={equivalent}_degraded_ok={degraded_ok}_"
        f"resume_ok={resume_ok}_retry_overhead={retry_overhead:.2f}_ok={ok}")


# ------------------------------------------------------------- framework
def bench_roofline() -> None:
    """Roofline terms per (arch x shape) from the dry-run artifacts."""
    from repro.analysis.report import roofline_table

    terms = roofline_table()
    if not terms:
        row("roofline", 0.0, "no_artifacts_run_dryrun_first")
        return
    for t in terms:
        row(f"roofline_{t.arch}_{t.shape}", t.step_time_s * 1e6,
            f"bound={t.bound}_frac={t.roofline_fraction:.3f}_useful="
            f"{t.useful_ratio:.2f}")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    want, us_ref = _timed(lambda: np.asarray(ref.attention_ref(q, k, v)))
    got = np.asarray(flash_attention(q, k, v, block_q=128, block_k=128,
                                     interpret=True))
    err = float(np.max(np.abs(got - want)))
    row("kernel_flash_attention", us_ref, f"maxerr={err:.1e}_vs_dense_ref")

    r = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.float32)
    kk = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.float32)
    vv = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.float32)
    w = jax.random.uniform(ks[0], (1, 64, 2, 16), jnp.float32, 0.1, 0.95)
    u = jax.random.normal(ks[1], (2, 16), jnp.float32)
    (want_y, _), us_ref = _timed(lambda: ref.wkv6_ref(r, kk, vv, w, u))
    got_y, _ = ops.wkv6(r, kk, vv, w, u, chunk=16, interpret=True)
    err = float(np.max(np.abs(np.asarray(got_y) - np.asarray(want_y))))
    row("kernel_wkv6", us_ref, f"maxerr={err:.1e}_vs_scan_ref")


def bench_train_step() -> None:
    import jax
    from repro.configs import get_config
    from repro.data import ByteCorpus, DataConfig
    from repro.models import get_model
    from repro.train import TrainConfig, init_train_state, make_train_step

    cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
    model = get_model(cfg)
    tc = TrainConfig()
    data = ByteCorpus(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                 global_batch=8))
    state, _ = init_train_state(model, jax.random.PRNGKey(0), tc)
    step = jax.jit(make_train_step(model, tc))
    batch = data.batch_at(0)
    state, m = step(state, batch)              # compile
    t0 = time.perf_counter_ns()
    for i in range(5):
        state, m = step(state, data.batch_at(i + 1))
    jax.block_until_ready(state)
    us = (time.perf_counter_ns() - t0) / 5e3
    row("train_step_smoke", us, f"loss={float(m['loss']):.3f}")


ALL_BENCHES = (bench_table1_coverage, bench_table3_validation,
               bench_fig2_reduction, bench_runtime_breakdown,
               bench_engine_speedup, bench_adaptive_speedup,
               bench_topology_query, bench_topology_http,
               bench_remote_discovery, bench_fault_recovery,
               bench_parallel_speedup, bench_pallas_interp, bench_fig5_stream,
               bench_perfmodel, bench_link_adjacency, bench_roofline,
               bench_kernels, bench_train_step)


def main(argv: list[str] | None = None) -> None:
    global JSON_MODE
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="emit a JSON array of rows on stdout instead of CSV")
    ap.add_argument("--out", default="bench_current.json",
                    help="also write the JSON rows to this file (default "
                         "bench_current.json — a git-ignored generated "
                         "artifact; pass --out '' to skip writing)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names "
                         "(e.g. engine_speedup,topology_query)")
    args = ap.parse_args(argv)
    JSON_MODE = args.json

    benches = ALL_BENCHES
    if args.only:
        wanted = {w.strip() for w in args.only.split(",") if w.strip()}
        benches = [fn for fn in ALL_BENCHES
                   if fn.__name__.removeprefix("bench_") in wanted]
        missing = wanted - {fn.__name__.removeprefix("bench_")
                            for fn in benches}
        if missing:
            ap.error(f"unknown benchmarks: {sorted(missing)}")

    for fn in benches:
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            # Same name a successful row would use, so the CI gate can match
            # a crashed gated bench and surface the exception in its report.
            row(fn.__name__.removeprefix("bench_"), 0.0,
                f"ERROR_{type(e).__name__}_{e}")

    if args.json:
        print(json.dumps(rows_as_json(), indent=2), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows_as_json(), f, indent=2)


if __name__ == "__main__":
    main()
