"""Bandwidth benchmarks (paper §IV-I) and collective probes (TPU extension).

The stream-pattern bandwidth probe is delegated to the runner (SimDevice
returns its configured value with noise; HostRunner times a jitted reduction/
copy; the TPU-target Pallas version lives in ``repro.kernels.stream_probe``).

``collective.py``-style probes are included here: on a real pod they time
``jax.lax`` collectives per mesh axis; without hardware they evaluate the
standard ring/bidirectional-torus analytic models against catalog constants —
the same numbers the roofline's collective term uses.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

__all__ = ["BandwidthResult", "measure_bandwidth",
           "CollectiveEstimate", "ring_all_reduce_time", "ring_all_gather_time",
           "all_to_all_time", "measure_collective"]


@dataclass(frozen=True)
class BandwidthResult:
    read_bw: float      # bytes/s
    write_bw: float     # bytes/s


def measure_bandwidth(runner, space: str) -> BandwidthResult:
    return BandwidthResult(
        read_bw=float(runner.bandwidth(space, "read")),
        write_bw=float(runner.bandwidth(space, "write")),
    )


# --------------------------------------------------------------------------
# Collective probes / analytic models
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class CollectiveEstimate:
    op: str
    bytes_moved: int
    n_devices: int
    seconds: float
    effective_bw: float     # bytes/s seen by one device


def ring_all_reduce_time(nbytes: int, n: int, link_bw: float) -> float:
    """Ring all-reduce: 2(n-1)/n * bytes across the slowest link."""
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * nbytes / link_bw


def ring_all_gather_time(nbytes_per_shard: int, n: int, link_bw: float) -> float:
    """Ring all-gather of n shards of ``nbytes_per_shard``."""
    if n <= 1:
        return 0.0
    return (n - 1) * nbytes_per_shard / link_bw


def all_to_all_time(nbytes_total: int, n: int, link_bw: float) -> float:
    """All-to-all where each device exchanges 1/n of its data with each peer;
    on a ring/torus the bisection constrains it to ~bytes*(n-1)/n / bw."""
    if n <= 1:
        return 0.0
    return (n - 1) / n * nbytes_total / link_bw


def measure_collective(op: str, nbytes: int, axis_size: int,
                       link_bw: float, repeats: int = 3) -> CollectiveEstimate:
    """Measure a collective across the live devices if >1 exist, otherwise
    fall back to the analytic torus model (documented provenance)."""
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    if len(devs) >= axis_size > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.compat import make_mesh

        mesh = make_mesh((axis_size,), ("x",))
        x = jnp.ones((axis_size, max(nbytes // 4 // axis_size, 1)), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P("x")))

        if op == "all_reduce":
            body = lambda v: jax.lax.psum(v, "x")
            out_spec = P("x")
        elif op == "all_gather":
            body = lambda v: jax.lax.all_gather(v, "x")
            out_spec = P("x")
        else:
            raise ValueError(f"unsupported live collective '{op}'")
        mapped = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                       out_specs=out_spec))
        mapped(x).block_until_ready()
        best = np.inf
        for _ in range(repeats):
            t0 = time.perf_counter_ns()
            mapped(x).block_until_ready()
            best = min(best, time.perf_counter_ns() - t0)
        secs = best * 1e-9
    else:
        if op == "all_reduce":
            secs = ring_all_reduce_time(nbytes, axis_size, link_bw)
        elif op == "all_gather":
            secs = ring_all_gather_time(nbytes // max(axis_size, 1), axis_size,
                                        link_bw)
        else:
            secs = all_to_all_time(nbytes, axis_size, link_bw)
    secs = max(secs, 1e-12)
    return CollectiveEstimate(op, nbytes, axis_size, secs, nbytes / secs)
