"""Watch the probe kernels that discovery launches.

``KernelTap`` puts a thin wrapper over the three probe kernels in their
modules (``TpuRunner`` looks them up there at each call).  A wrapper calls
the kernel unchanged and returns its output unchanged; it counts the
launch, counts the bytes a stream launch needs from its shape, and, while
``keep`` is set, keeps the inputs and outputs for the check that follows
the window.  ``interpret`` runs the kernels in Pallas' interpreter (the
CPU rehearsal); ``fault`` alters what a kernel returns (the tests).
"""
from __future__ import annotations

from .arith import stream_bytes

KERNELS = {"pchase_kernel_batch": "repro.kernels.pchase_probe",
           "stream_read_kernel": "repro.kernels.stream_probe",
           "stream_write_kernel": "repro.kernels.stream_probe"}


class KernelTap:
    def __init__(self, interpret: bool = False, fault=None):
        self.interpret = interpret
        self.fault = fault              # (kernel name, args, out) -> out
        self.keep = False
        self.calls = {k: 0 for k in KERNELS}
        self.stream_bytes = 0
        self.kept: list[tuple] = []
        self._orig: dict[str, object] = {}

    def __enter__(self) -> "KernelTap":
        import importlib

        for name, mod in KERNELS.items():
            m = importlib.import_module(mod)
            self._orig[name] = getattr(m, name)
            setattr(m, name, self._wrap(name, self._orig[name]))
        return self

    def __exit__(self, *exc) -> None:
        import importlib

        for name, fn in self._orig.items():
            setattr(importlib.import_module(KERNELS[name]), name, fn)

    def _wrap(self, name: str, fn):
        def tapped(*args, **kw):
            if self.interpret:
                kw["interpret"] = True
            out = fn(*args, **kw)
            if self.fault is not None:
                out = self.fault(name, args, kw, out)
            self.calls[name] += 1
            if name != "pchase_kernel_batch":
                x = args[0]
                self.stream_bytes += stream_bytes(
                    "write" if name == "stream_write_kernel" else "read",
                    x.shape, x.dtype.itemsize)
            if self.keep:
                if name == "stream_write_kernel":
                    # One copy of the array is enough to check, and each
                    # kept copy holds as much device memory as the stream.
                    self.kept = [k for k in self.kept if k[0] != name]
                self.kept.append((name, args, kw, out))
            return out

        return tapped

    def reset(self) -> None:
        self.calls = {k: 0 for k in KERNELS}
        self.stream_bytes = 0

    @property
    def launches(self) -> int:
        return sum(self.calls.values())
