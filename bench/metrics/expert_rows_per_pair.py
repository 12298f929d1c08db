"""Rows handed to the expert layer's matmuls per (token, held expert) pair
routed, over the window (expert layer): the program's ``moe_rows`` over
``moe_pairs``.  1.0 where the matmuls see only the held pairs' rows; pair
rows of experts held elsewhere, padded tokens or padding to a capacity
read above it (a grouped matmul over all ``t*k`` pair rows, of which a
share ``held/E`` is held here, reads about ``E/held``)."""


def read(record):
    c = record["counters"]
    if not c.get("moe_pairs"):
        return None
    return c["moe_rows"] / c["moe_pairs"]
