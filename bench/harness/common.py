"""Shared plumbing of a run: files found by name, the device, compiles,
seeds and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """Import ``bench/<parts>`` by path; file names may hold ``-`` and
    ``.``, which ``import`` cannot spell."""
    path = os.path.join(BENCH, *parts)
    name = "bench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> tuple[dict, dict]:
    """(workload entry, configuration entry) for a cell name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def seed32(seed: int, salt: int = 0) -> int:
    """A 32-bit seed from any whole number, however large."""
    import numpy as np

    return int(np.random.SeedSequence([int(seed) % (1 << 63), salt])
               .generate_state(1)[0])


def check_device(chips: int) -> dict:
    """The TPU the cell asks for, with its published peaks.

    Exits non-zero, before any result is printed, when JAX's devices are
    not TPUs, when there are fewer than ``chips``, or when the kind is
    missing from ``peaks.json`` (an unknown kind is an error, never a
    default)."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX's first device is "
                       f"{dev.platform!r} ({dev.device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                       f"{len(devs)}")
    table = load_json("peaks.json")
    if dev.device_kind not in table["kinds"]:
        raise SystemExit(f"bench: device kind {dev.device_kind!r} is not in "
                       f"bench/peaks.json; add its published peaks")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips, "peaks": table["kinds"][dev.device_kind]}


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every compile however short."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CACHE_DIR


class CompileLog:
    """Backend compiles seen by JAX's monitoring hooks."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def peak_memory_bytes(count: int) -> int | None:
    """Peak bytes in use on the fullest of the first ``count`` devices."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:count]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    stderr, and the result as the last line of stdout, ``checks`` last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)


def checks_pass(checks: dict) -> bool:
    """Every number within its limit; a ``"> 0"`` limit asks for at least
    one comparison made."""
    ok = True
    for c in checks.values():
        if c["limit"] == "> 0":
            ok &= c["value"] > 0
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)
