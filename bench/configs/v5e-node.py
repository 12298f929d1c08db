"""Plain reference for the ``v5e-node`` deployment: what chip discovery
must answer, written without the program.

* A pointer chase over a buffer walks ``cursor = buf[cursor]`` from slot 0
  for ``steps`` loads and returns ``[final cursor, int32 sum of cursors]``.
* A read stream returns each block's sum; a write stream returns
  ``x + 1``.
* A discovered topology names the chip, carries the measured HBM latency
  and bandwidths with benchmark provenance, bandwidths within the
  published peak, and the VMEM/SMEM capacities and core count that the TPU
  runtime reports.
"""
from __future__ import annotations

import math

import numpy as np


def chase(buf, steps: int) -> tuple[int, int]:
    """``steps`` dependent loads over row 0 of ``buf`` from slot 0."""
    p = np.asarray(buf).reshape(-1)
    cursor, total = 0, 0
    for _ in range(int(steps)):
        cursor = int(p[cursor])
        total = (total + cursor + 2**31) % 2**32 - 2**31
    return cursor, total


def stream_read(x, block_rows: int):
    """Per-block float32 sums of a ``(rows, cols)`` array, on its device."""
    import jax.numpy as jnp

    rows, cols = x.shape
    return jnp.sum(x.reshape(rows // block_rows, block_rows * cols)
                   .astype(jnp.float32), axis=1)


def stream_write(x):
    return x + 1


def runtime_capacities() -> dict:
    """What the TPU runtime reports for the attached chip."""
    from jax.experimental.pallas import tpu as pltpu

    info = pltpu.get_tpu_info()
    return {"VMEM": int(info.vmem_capacity_bytes),
            "SMEM": int(info.smem_capacity_bytes),
            "tensor_cores": int(info.num_cores)}


def topology_faults(doc: dict, device_kind: str, hbm_peak: float,
                    capacities: dict) -> list[str]:
    """Every way a stored topology document departs from what discovery
    of this chip must report; empty when it is sound."""
    faults = []
    if doc.get("backend") != f"pallas-tpu:{device_kind}":
        faults.append(f"backend {doc.get('backend')!r}")
    mem = {m["name"]: m for m in doc.get("memory", [])}
    if set(mem) != {"DeviceMemory", "VMEM", "SMEM"}:
        faults.append(f"memory elements {sorted(mem)}")
    dm = mem.get("DeviceMemory", {}).get("attrs", {})
    for attr, unit in (("load_latency", "ns"), ("read_bw", "GB/s"),
                       ("write_bw", "GB/s")):
        a = dm.get(attr)
        if a is None or a.get("unit") != unit \
                or a.get("provenance") != "benchmark":
            faults.append(f"DeviceMemory.{attr} {a!r}")
            continue
        v = a["value"]
        if not (isinstance(v, (int, float)) and math.isfinite(v) and v > 0):
            faults.append(f"DeviceMemory.{attr} = {v!r}")
        elif unit == "GB/s" and v * 1e9 > 1.05 * hbm_peak:
            faults.append(f"DeviceMemory.{attr} = {v} GB/s over the peak")
    for name in ("VMEM", "SMEM"):
        a = mem.get(name, {}).get("attrs", {}).get("size")
        if a is None or a.get("value") != capacities[name] \
                or a.get("provenance") != "api":
            faults.append(f"{name}.size {a!r} != {capacities[name]}")
    cores = {c["name"]: c["count"] for c in doc.get("compute", [])}
    if cores.get("tensor_cores") != capacities["tensor_cores"]:
        faults.append(f"tensor_cores {cores.get('tensor_cores')!r}")
    return faults

