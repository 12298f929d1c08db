"""The decode program's share of its roofline, in %: the least time of
every decode step in the window, ``max(flops / peak, bytes / bandwidth)``
at its position (``harness.arith``), over the device time of the programs
launched from the ``bench.decode`` spans."""
from harness.arith import Dense, decode_step_floor_s


def read(record):
    c = record["counters"]
    ns = record["trace"]["by_span"].get("bench.decode", {}).get("ns", 0)
    if not c.get("waves") or ns <= 0:
        return None
    m = Dense.from_config(record["config"])
    floor = c["waves"] * sum(
        decode_step_floor_s(m, c["slots"], c["prompt_len"] + j,
                            record["peaks"])
        for j in range(c["max_new"]))
    return 100.0 * floor / (ns * 1e-9)
