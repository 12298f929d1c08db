"""TpuRunner: the ProbeRunner that measures the attached TPU.

This is the chip path of ``discover_pallas`` (``interpret=False``).  Nothing
on it is modeled: there is no ``SimDevice``, no calibration chain and no
chain-length factor.  The runner advertises only what it measures, plus the
capacities the runtime reports:

* **DeviceMemory load latency** — ``pchase_kernel_batch`` walks a random
  single cycle laid out at the requested stride over the requested
  footprint of an HBM buffer (slot i sits at byte ``i * stride``).  Each
  load is one row DMA HBM -> SMEM.  A sample is the slope between two chain
  lengths, each timed around ``block_until_ready``:
  ``(t_long - t_short) / (long - short)``, so host dispatch cancels.
* **DeviceMemory read / write bandwidth** — the stream kernels over a
  buffer far larger than VMEM (1 GiB by default); bytes moved over the
  best-of-``REPS`` wall time.  ``write`` counts the bytes read and written
  by the copy.  Dispatch stays in these walls, so they are lower bounds.
* **VMEM / SMEM capacities** — ``pltpu.get_tpu_info()``, the TPU runtime's
  table keyed by ``device_kind``, entered with API provenance (MT4G takes
  such sizes from the vendor API).

The runner refuses to start unless JAX's first device is a TPU: a CPU
never stands in for the chip.

Profiler spans: ``mt4g.runner.init`` (device lookup, ``get_tpu_info``),
``mt4g.chase.build`` (a chase buffer built on the host, its puts to the
device issued), ``mt4g.stream.fill`` (the stream's fill issued) and
``mt4g.launch``, one per unit of ``kernel_calls``: a kernel's dispatch
through ``block_until_ready``.  Puts and fill are not waited for: the next
launch waits for them, as it did before the spans.
"""
from __future__ import annotations

import time

import numpy as np

from ...tracing import span
from ..topology import PROVENANCE_API, ComputeElement, MemoryElement
from .runners import SpaceInfo, random_cycle

__all__ = ["TpuRunner", "strided_cycle"]

DEVICE_MEMORY = "DeviceMemory"
ELEM_BYTES = 4                   # int32 chase indices


def strided_cycle(array_bytes: int, stride: int, rng: np.random.Generator,
                  lanes: int = 128) -> tuple[np.ndarray, int]:
    """(buffer, slots): a (1, W) int32 chase buffer holding a random single
    cycle over the ``slots`` slots at byte offsets ``0, stride, 2*stride,
    ...`` of an ``array_bytes`` footprint.  Each slot holds the element
    index of the next; the chain starts at 0.  ``W`` is rounded up to whole
    ``lanes``-element rows."""
    stride_elems = max(int(stride) // ELEM_BYTES, 1)
    slots = max(int(array_bytes) // (stride_elems * ELEM_BYTES), 2)
    pos = np.arange(slots, dtype=np.int64) * stride_elems
    width = -(-slots * stride_elems // lanes) * lanes
    buf = np.zeros((1, width), dtype=np.int32)
    buf[0, pos] = pos[random_cycle(slots, rng)]
    return buf, slots


class TpuRunner:
    """ProbeRunner over the probe kernels compiled for the attached TPU.

    ``BASE_STEPS`` is the shorter chain of each latency sample (the longer
    one is twice as long, and both cover the footprint at least once);
    ``STREAM_BYTES`` the size of each bandwidth stream; ``REPS`` the number
    of timed streams behind each bandwidth value.
    """

    BASE_STEPS = 2048
    STREAM_BYTES = 1 << 30
    STREAM_COLS = 1024           # f32 lanes per stream row (8 x 128)
    STREAM_BLOCK_ROWS = 2048     # 8 MiB blocks
    REPS = 5
    deterministic = False        # samples are real wall-time measurements
    interpret = False

    def __init__(self):
        import jax
        from jax.experimental.pallas import tpu as pltpu

        with span("mt4g.runner.init"):
            device = jax.devices()[0]
            if device.platform != "tpu":
                raise RuntimeError(
                    f"TpuRunner measures a TPU, but JAX's first device is "
                    f"{device.platform!r} ({device.device_kind}); the "
                    f"modeled interpreter path is "
                    f"discover_pallas(interpret=True)")
            self.device = device
            self.device_kind = device.device_kind
            self.info = pltpu.get_tpu_info()
        self._rng = np.random.default_rng(0)
        self._chase: dict[tuple[int, int], tuple] = {}
        self._stream = None
        self.kernel_calls = 0

    # ------------------------------------------------------------- spaces
    def spaces(self) -> list[SpaceInfo]:
        """No cache-family space: the v5e has no cache between VMEM and
        HBM, and VMEM/SMEM capacities come from the API."""
        return []

    def api_elements(self) -> list:
        """Elements the runtime reports, with API provenance."""
        out = []
        for name, size in (("VMEM", self.info.vmem_capacity_bytes),
                           ("SMEM", self.info.smem_capacity_bytes)):
            me = MemoryElement(name, "scratchpad", "core")
            me.set("size", int(size), "B", PROVENANCE_API)
            out.append(me)
        out.append(ComputeElement("tensor_cores", int(self.info.num_cores)))
        return out

    # -------------------------------------------------------------- chase
    def _chase_args(self, array_bytes: int, stride: int) -> tuple:
        """Device-resident chase buffer + the two chain lengths for one
        (footprint, stride) request, built (and its kernel compiled and
        warmed) once per request shape."""
        key = (int(array_bytes), int(stride))
        args = self._chase.get(key)
        if args is None:
            import jax

            from repro.kernels.pchase_probe import LANES

            with span("mt4g.chase.build"):
                buf, slots = strided_cycle(array_bytes, stride, self._rng,
                                           LANES)
                short = max(self.BASE_STEPS, slots)
                put = lambda a: jax.device_put(a, self.device)  # noqa: E731
                args = (put(buf), put(np.array([short], np.int32)),
                        put(np.array([2 * short], np.int32)), short)
            self._chase[key] = args
            self._timed_chase(args[0], args[1])
            self._timed_chase(args[0], args[2])
        return args

    def _timed_chase(self, buf, steps) -> int:
        from repro.kernels.pchase_probe import pchase_kernel_batch

        with span("mt4g.launch"):
            t0 = time.perf_counter_ns()
            pchase_kernel_batch(buf, steps).block_until_ready()
            dt = time.perf_counter_ns() - t0
        self.kernel_calls += 1
        return dt

    def pchase(self, space, array_bytes, stride, n_samples):
        """``n_samples`` per-load latencies (ns) over one footprint."""
        if space != DEVICE_MEMORY:
            raise NotImplementedError(f"tpu runner: no space {space!r}")
        buf, short_steps, long_steps, short = self._chase_args(array_bytes,
                                                               stride)
        out = np.empty(int(n_samples))
        for s in range(out.size):
            t_short = self._timed_chase(buf, short_steps)
            t_long = self._timed_chase(buf, long_steps)
            out[s] = (t_long - t_short) / short
        return out

    # ---------------------------------------------------------- bandwidth
    def bandwidth(self, space, mode="read"):
        """Bytes/s of the stream kernels over ``STREAM_BYTES`` of HBM."""
        import jax.numpy as jnp

        from repro.kernels.stream_probe import (stream_read_kernel,
                                                stream_write_kernel)

        if space != DEVICE_MEMORY:
            raise NotImplementedError(f"tpu runner: no space {space!r}")
        if self._stream is None:
            rows = self.STREAM_BYTES // (4 * self.STREAM_COLS)
            with span("mt4g.stream.fill"):
                self._stream = jnp.ones((rows, self.STREAM_COLS),
                                        jnp.float32, device=self.device)
        x = self._stream
        fn = stream_read_kernel if mode == "read" else stream_write_kernel
        with span("mt4g.launch"):
            fn(x, block_rows=self.STREAM_BLOCK_ROWS).block_until_ready()
        best = np.inf
        for _ in range(self.REPS):
            with span("mt4g.launch"):
                t0 = time.perf_counter_ns()
                fn(x, block_rows=self.STREAM_BLOCK_ROWS).block_until_ready()
                best = min(best, time.perf_counter_ns() - t0)
        self.kernel_calls += self.REPS + 1
        moved = x.size * 4 * (2 if mode == "write" else 1)
        return moved / (best * 1e-9)
