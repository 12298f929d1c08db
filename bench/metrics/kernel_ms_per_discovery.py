"""Device milliseconds of the probe kernels per discovery, from the
trace: the programs ``pchase_kernel_batch``, ``stream_read_kernel`` and
``stream_write_kernel`` (kernels layer)."""
from harness.trace import module_ns

PROBES = ("pchase_kernel_batch", "stream_read_kernel", "stream_write_kernel")


def read(record):
    ns, launches = module_ns(record["trace"], *PROBES)
    n = record["counters"].get("discoveries")
    if not n or not launches:
        return None
    return ns * 1e-6 / n
