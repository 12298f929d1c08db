"""The CPU rehearsal: the chip path driven on the CPU at tiny sizes.

``--rehearse 1`` runs a cell end to end with ``JAX_PLATFORMS=cpu``: the
probe kernels in Pallas' interpreter, the model at a few widths of its
family.  It checks paths, arguments and the comparison that decides
``correct``; it prints no device metric, and never stands in for the chip.
"""
from __future__ import annotations

from types import SimpleNamespace


def cpu_runner_class():
    """``TpuRunner`` with the platform check lifted, VMEM/SMEM sizes of a
    v5e, and a 4 MiB stream: the runner's own code on the CPU."""
    import jax
    import numpy as np

    from repro.core.probes import TpuRunner

    class CpuRehearsalRunner(TpuRunner):
        STREAM_BYTES = 4 << 20
        STREAM_BLOCK_ROWS = 256

        def __init__(self):                     # noqa: D401 — no TPU check
            self.device = jax.devices()[0]
            self.device_kind = "cpu-rehearsal"
            self.info = SimpleNamespace(vmem_capacity_bytes=128 << 20,
                                        smem_capacity_bytes=1 << 20,
                                        num_cores=1)
            self._rng = np.random.default_rng(0)
            self._chase = {}
            self._stream = None
            self.kernel_calls = 0

    return CpuRehearsalRunner


REHEARSAL_DEVICE = {"platform": "cpu", "kind": "cpu-rehearsal", "count": 1,
                    "peaks": {"bf16_flops_per_s": 1e30,
                              "hbm_bytes_per_s": 1e30}}
