"""The stream kernels' share of the HBM roofline, in %: the bytes their
launches need (``harness.arith.stream_bytes``, from the arrays' shapes)
over their device time in the trace, over the chip's HBM bandwidth."""
from harness.trace import module_ns


def read(record):
    ns, launches = module_ns(record["trace"], "stream_read_kernel",
                             "stream_write_kernel")
    moved = record["counters"].get("stream_bytes")
    if not launches or not moved or ns <= 0:
        return None
    return 100.0 * moved / (ns * 1e-9) / record["peaks"]["hbm_bytes_per_s"]
