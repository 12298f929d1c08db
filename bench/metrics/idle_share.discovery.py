"""Share of the traced window in which no operation ran on the device,
in %, in the discovery cells (device layer)."""


def read(record):
    t = record["trace"]
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
