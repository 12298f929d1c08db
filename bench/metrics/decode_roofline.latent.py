"""The latent decode program's share of its roofline, in %: the least time
of every decode step in the window, ``max(flops / peak, bytes /
bandwidth)`` at its position (``harness.arith_latent``: the absorbed form,
the latent cache at ``rank + rope`` values a position, every held expert's
weights), over the device time of the programs launched from the
``bench.decode`` spans.  The routed experts' operations take the pairs per
token that the program counted over the window."""
from harness.arith_latent import Latent


def read(record):
    c = record["counters"]
    ns = record["trace"]["by_span"].get("bench.decode", {}).get("ns", 0)
    if not c.get("waves") or not c.get("moe_pairs") or ns <= 0:
        return None
    m = Latent.from_config(record["config"])
    per_token = c["moe_pairs"] / (
        c["waves"] * c["slots"] * (c["prompt_len"] + c["max_new"]))
    floor = c["waves"] * sum(
        m.decode_step_floor_s(c["slots"], c["prompt_len"] + j, per_token,
                              record["peaks"])
        for j in range(c["max_new"]))
    return 100.0 * floor / (ns * 1e-9)
