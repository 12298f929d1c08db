#!/usr/bin/env python3
"""Prove the system starts on a TPU: probe kernels, discovery, a full serve.

    python3 chip_smoke.py [--out DIR]

Everything runs in this one process, which holds the chip; it starts no
child that touches JAX.  The phases, in order:

1. device — JAX's first device must be a TPU whose ``device_kind`` is in
   ``chip_peaks.json`` (published peaks, with their source);
2. kernels — every probe kernel on seeded inputs, compared with its
   reference: ``pchase_reference`` / ``eviction_reference`` for the chases,
   numpy for the streams;
3. cold discovery — ``discover_pallas()`` (the chip path) into a fresh
   ``TopologyStore`` under ``--out``: HBM read/write bandwidth in
   ``(0, 1.05 x peak]``, a finite positive HBM latency, API-provenance
   VMEM/SMEM capacities, nothing else;
4. warm discovery — the same request is a store hit with 0 kernel
   launches, and ``hbm.bandwidth`` reads back through ``TopologyService``;
5. serve — ``repro.launch.serve`` with internlm2-1.8b at its published
   widths (bf16, random weights from seed 0): 4 requests of 256 prompt
   tokens, 32 new tokens each.

A failed check raises, so the exit code is non-zero and the result line
never prints.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
``--out`` also receives ``chip_smoke.json`` with every figure printed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(ROOT, "chip_peaks.json")
sys.path.insert(0, os.path.join(ROOT, "src"))

SERVE_ARGV = ["--arch", "internlm2-1.8b", "--mesh", "1x1", "--requests", "4",
              "--prompt-len", "256", "--max-new", "32", "--max-len", "512"]
N_SAMPLES = 9


class CompileLog:
    """Backend compiles seen by JAX's monitoring hooks, per phase."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits


def run_phase(name: str, log: CompileLog, report: dict, fn, *args):
    """Run one phase; record its wall and compile figures in ``report``."""
    c0, s0, h0 = log.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)
    wall = time.perf_counter() - t0
    c1, s1, h1 = log.snapshot()
    report["phases"][name] = {"wall_s": wall, "compiles": c1 - c0,
                              "compile_s": s1 - s0,
                              "persistent_cache_hits": h1 - h0}
    print(f"[{name}] wall {wall:.3f} s, {c1 - c0} compiles "
          f"({s1 - s0:.3f} s, {h1 - h0} from the persistent cache)",
          flush=True)
    return out


# ----------------------------------------------------------------- phases
def check_device(report: dict):
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    with open(PEAKS) as f:
        table = json.load(f)
    if dev.device_kind not in table["kinds"]:
        raise KeyError(f"device kind {dev.device_kind!r} is not in "
                       f"{PEAKS}; add its published peaks with a source")
    peaks = table["kinds"][dev.device_kind]
    report["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    report["peaks"] = dict(peaks, source=table["source"])
    print(f"[device] {dev.platform} {dev.device_kind!r} x{len(jax.devices())};"
          f" published peaks: {peaks['hbm_bytes_per_s'] / 1e9:.0f} GB/s HBM, "
          f"{peaks['bf16_flops_per_s'] / 1e12:.0f} TFLOP/s bf16 "
          f"({table['source']})", flush=True)
    return peaks


def check_kernels(report: dict, interpret=False, stream_rows: int = 16384,
                  stream_block_rows: int = 2048, chase_width: int = 1 << 20):
    """Every probe kernel on seeded inputs vs its reference."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.probes.runners import random_cycle
    from repro.kernels.pchase_probe import (eviction_kernel_batch,
                                            eviction_reference, pchase_kernel,
                                            pchase_kernel_batch,
                                            pchase_reference)
    from repro.kernels.stream_probe import (stream_read_kernel,
                                            stream_write_kernel)

    rng = np.random.default_rng(0)
    perm = random_cycle(chase_width, rng)
    got = np.asarray(pchase_kernel(jnp.asarray(perm), iters=5000,
                                   interpret=interpret))
    assert tuple(got.tolist()) == pchase_reference(perm, 5000), got

    rows, width = 8, chase_width // 16
    perms = np.zeros((rows, width), np.int32)
    evictors = np.zeros((rows, width), np.int32)
    for i in range(rows):
        n = width >> (i % 4)
        perms[i, :n] = random_cycle(n, rng)
        evictors[i, :n] = random_cycle(n, rng)
    steps = rng.integers(1, 3000, rows).astype(np.int32)
    warm = rng.integers(0, 2000, rows).astype(np.int32)
    warm[0] = 0
    got = np.asarray(pchase_kernel_batch(jnp.asarray(perms),
                                         jnp.asarray(steps),
                                         interpret=interpret))
    want = [pchase_reference(perms[i], steps[i]) for i in range(rows)]
    assert [tuple(r) for r in got.tolist()] == want, got
    got_ev = np.asarray(eviction_kernel_batch(
        jnp.asarray(perms), jnp.asarray(evictors), jnp.asarray(warm),
        jnp.asarray(steps), interpret=interpret))
    want_ev = [eviction_reference(perms[i], evictors[i], warm[i], steps[i])
               for i in range(rows)]
    assert [tuple(r) for r in got_ev.tolist()] == want_ev, got_ev
    assert tuple(got_ev[0]) == tuple(got[0])      # warm 0 == plain chase

    x = rng.integers(0, 3, (stream_rows, 1024)).astype(np.float32)
    xd = jnp.asarray(x)
    sums = np.asarray(stream_read_kernel(xd, block_rows=stream_block_rows,
                                         interpret=interpret))
    np.testing.assert_array_equal(
        sums, x.reshape(-1, stream_block_rows * 1024).sum(1))
    y = np.asarray(stream_write_kernel(xd, block_rows=stream_block_rows,
                                       interpret=interpret))
    np.testing.assert_array_equal(y, x + 1)
    report["kernels"] = {"pchase_kernel": "ok", "pchase_kernel_batch": "ok",
                         "eviction_kernel_batch": "ok",
                         "stream_read_kernel": "ok",
                         "stream_write_kernel": "ok",
                         "stream_bytes": int(x.nbytes)}
    print(f"[kernels] 5 probe kernels equal their references "
          f"(chase over {chase_width * 4 >> 20} MiB, streams over "
          f"{x.nbytes >> 20} MiB)", flush=True)


def cold_discovery(report: dict, store, runner, peaks: dict):
    from repro.core import discover_pallas

    topo, _ = discover_pallas(runner=runner, n_samples=N_SAMPLES, store=store)
    kind = report["device"]["kind"]
    assert topo.backend == f"pallas-tpu:{kind}", topo.backend
    assert topo.general["clock_domain"].value == "ns"
    assert {m.name for m in topo.memory} == {"DeviceMemory", "VMEM", "SMEM"}
    found = {}
    for me in topo.memory:
        for attr, a in me.attrs.items():
            assert a.provenance in ("benchmark", "api"), (me.name, attr, a)
            found[f"{me.name}.{attr}"] = {"value": a.value, "unit": a.unit,
                                          "provenance": a.provenance}
            print(f"  {me.name}.{attr} = {a.value} {a.unit} "
                  f"({a.provenance})", flush=True)
    dm = topo.find_memory("DeviceMemory")
    peak = peaks["hbm_bytes_per_s"]
    for attr in ("read_bw", "write_bw"):
        bw = float(dm.get(attr)) * 1e9
        assert 0 < bw <= 1.05 * peak, (attr, bw, peak)
        print(f"  {attr}: {bw / 1e9:.1f} GB/s = {bw / peak:.3f} of the "
              f"published {peak / 1e9:.0f} GB/s", flush=True)
    lat = float(dm.get("load_latency"))
    assert math.isfinite(lat) and lat > 0, lat
    for name in ("VMEM", "SMEM"):
        me = topo.find_memory(name)
        assert me.get("size") > 0 and me.attrs["size"].provenance == "api"
    report["discovery"] = {"backend": topo.backend, "attributes": found,
                           "kernel_calls": runner.kernel_calls}
    return topo


def warm_discovery(report: dict, store, runner, topo):
    from repro.core import discover_pallas
    from repro.core.discover import tpu_request_descriptor
    from repro.core.engine.store import request_key
    from repro.serve.topology_service import TopologyService

    calls = runner.kernel_calls
    again, _ = discover_pallas(runner=runner, n_samples=N_SAMPLES,
                               store=store)
    assert runner.kernel_calls == calls, (runner.kernel_calls, calls)
    assert again.to_json() == topo.to_json()
    key = request_key(tpu_request_descriptor(runner.device_kind, N_SAMPLES))
    res = TopologyService(store).query(key, "hbm.bandwidth")
    want = topo.find_memory("DeviceMemory").get("read_bw")
    assert res.found and res.value == want, res
    report["warm"] = {"kernel_launches": 0, "hbm.bandwidth": res.value,
                      "key": key}
    print(f"[warm] store hit, 0 kernel launches; hbm.bandwidth = "
          f"{res.value} {res.unit} via TopologyService", flush=True)


def serve_model(report: dict, argv: list[str]):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve

    res = serve.run(argv)
    cfg = get_config(res["arch"])
    outs = res["outputs"]
    n_req = int(argv[argv.index("--requests") + 1])
    max_new = int(argv[argv.index("--max-new") + 1])
    assert len(outs) == n_req
    for o in outs:
        o = np.asarray(o)
        assert o.shape == (max_new,), o.shape
        assert o.min() >= 0 and o.max() < cfg.vocab_size
    stats = jax.devices()[0].memory_stats() or {}
    toks = sum(int(np.asarray(o).size) for o in outs)
    report["serve"] = {
        "arch": cfg.name, "n_layers": cfg.n_layers, "d_model": cfg.d_model,
        "vocab_size": cfg.vocab_size, "dtype": res["dtype"],
        "requests": n_req, "new_tokens": toks, "wall_s": res["wall_s"],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(f"[serve] {cfg.name} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}, {res['dtype']}): "
          f"{n_req} requests, {toks} new tokens in {res['wall_s']:.3f} s "
          f"(compiles included); peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')}", flush=True)


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for the topology store and the report")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    report = {"compile_cache": enable_compile_cache(), "phases": {}}
    log = CompileLog()
    peaks = run_phase("device", log, report, check_device, report)

    from repro.core.engine.store import TopologyStore
    from repro.core.probes import TpuRunner

    run_phase("kernels", log, report, check_kernels, report)
    store_dir = os.path.join(args.out, "store")
    shutil.rmtree(store_dir, ignore_errors=True)
    store = TopologyStore(store_dir)
    runner = TpuRunner()
    report["tpu_info"] = {"vmem_capacity_bytes":
                          runner.info.vmem_capacity_bytes,
                          "smem_capacity_bytes":
                          runner.info.smem_capacity_bytes,
                          "num_cores": runner.info.num_cores}
    topo = run_phase("cold_discovery", log, report, cold_discovery, report,
                     store, runner, peaks)
    run_phase("warm_discovery", log, report, warm_discovery, report, store,
              runner, topo)
    del runner                    # frees its 1 GiB stream before the model
    run_phase("serve", log, report, serve_model, report, SERVE_ARGV)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
