"""Chip discovery as the cells drive it, and the check of what it made.

Each discovery is what a separate request would pay: ``discover_pallas``
with ``refresh=True`` into the store and a fresh runner, so it builds its
own chase buffer and stream and launches every probe.  The kernels stay
compiled in the process.
"""
from __future__ import annotations

import shutil
import sys
import tempfile

import numpy as np

from .common import load_module
from .probes import KernelTap


class Discoveries:
    """Back-to-back discoveries of the attached chip into one store."""

    def __init__(self, ctx, config: dict, sampled: set[int]):
        from repro.core.engine.store import TopologyStore

        self.ctx = ctx
        self.n_samples = int(config["discovery"]["n_samples"])
        self.ref = load_module("configs", f"{ctx.config_name}.py")
        self.root = tempfile.mkdtemp(prefix="bench-store-")
        self.store = TopologyStore(self.root)
        if ctx.rehearse:
            from .rehearsal import cpu_runner_class
            self.make_runner = cpu_runner_class()
        else:
            from repro.core.probes import TpuRunner
            self.make_runner = TpuRunner
        self.tap = KernelTap(interpret=ctx.rehearse,
                             fault=ctx.faults.get("kernel"))
        self.sampled = sampled
        self.count = 0              # discoveries in the window
        self.kernel_calls = 0
        self.failed = 0
        self.docs: list[dict] = []          # every returned topology
        self.checked: list[dict] = []       # sampled: kernels + read-back
        self.key = None

    def __enter__(self) -> "Discoveries":
        self.tap.__enter__()
        put = self.ctx.faults.get("store_put")
        if put is not None:
            put(self.store)
        return self

    def __exit__(self, *exc) -> None:
        self.tap.__exit__()
        shutil.rmtree(self.root, ignore_errors=True)

    def once(self, index: int | None) -> dict | None:
        """One discovery; ``index`` is its number in the window (None in
        set-up).  Returns its topology document, or None if it raised."""
        from repro.core import discover_pallas

        keep = index is not None and index in self.sampled
        self.tap.keep = keep
        try:
            with self.ctx.span("bench.discovery"):
                runner = self.make_runner()
                topo, _ = discover_pallas(runner=runner,
                                          n_samples=self.n_samples,
                                          store=self.store, refresh=True)
        except Exception as e:          # noqa: BLE001 — a failed request
            print(f"bench: discovery failed: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            self.failed += 1
            self.tap.keep = False
            self.tap.kept = []
            return None
        doc = topo.to_json()
        if self.key is None:
            from repro.core.discover import tpu_request_descriptor
            from repro.core.engine.store import request_key
            self.key = request_key(tpu_request_descriptor(
                runner.device_kind, self.n_samples))
        if index is not None:
            self.count += 1
            self.kernel_calls += runner.kernel_calls
            self.docs.append(doc)
        if keep:
            stored = self.store.get(self.key)
            self.checked.append({
                "index": index, "returned": doc,
                "stored": None if stored is None else
                stored.topology.to_json(),
                "kernels": self.tap.kept})
            self.tap.kept = []
            self.tap.keep = False
        return doc

    # ------------------------------------------------------------- check
    def check(self, device: dict) -> dict:
        """The numbers compared after the window, each with limit 0."""
        ref = self.ref
        if self.ctx.rehearse:
            caps = {"VMEM": 128 << 20, "SMEM": 1 << 20, "tensor_cores": 1}
        else:
            caps = ref.runtime_capacities()
        peak = device["peaks"]["hbm_bytes_per_s"]
        topo_faults = sum(len(ref.topology_faults(d, device["kind"], peak,
                                                  caps))
                          for d in self.docs)
        chase_bad = stream_bad = persisted_bad = compared = 0
        walks: dict = {}
        for rec in self.checked:
            persisted_bad += rec["stored"] != rec["returned"]
            for name, args, kw, out in rec["kernels"]:
                compared += 1
                if name == "pchase_kernel_batch":
                    buf, steps = args[0], args[1]
                    k = (id(buf), int(np.asarray(steps)[0]))
                    if k not in walks:
                        walks[k] = ref.chase(np.asarray(buf), k[1])
                    got = tuple(int(v) for v in np.asarray(out).reshape(-1))
                    chase_bad += got != walks[k]
                elif name == "stream_read_kernel":
                    want = ref.stream_read(args[0], kw["block_rows"])
                    stream_bad += int(np.sum(np.asarray(out)
                                             != np.asarray(want)))
                else:
                    import jax.numpy as jnp
                    stream_bad += int(jnp.sum(out != ref.stream_write(
                        args[0])))
        self.checked = []
        return {
            "discoveries_failed": {"value": self.failed, "limit": 0},
            "topology_faults": {"value": topo_faults, "limit": 0},
            "persisted_mismatch": {"value": persisted_bad, "limit": 0},
            "chase_mismatch": {"value": chase_bad, "limit": 0},
            "stream_mismatch": {"value": stream_bad, "limit": 0},
            "kernel_outputs_compared": {"value": compared,
                                        "limit": "> 0"},
        }

