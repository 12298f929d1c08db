"""Probe kernel launches per discovery: ``TpuRunner.kernel_calls`` over
the discoveries completed in the window (runners layer)."""


def read(record):
    c = record["counters"]
    if not c.get("discoveries"):
        return None
    return c["kernel_calls"] / c["discoveries"]
