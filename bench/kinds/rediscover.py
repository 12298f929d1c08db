"""Traffic kind ``rediscover``: back-to-back chip re-discoveries.

Parameters (``bench/traffic/<mix>.json``): ``sampled`` discoveries, drawn
from the seed among the first ``sample_from``, have every kernel output
and their persisted topology compared after the window; every discovery's
topology is checked.

End-to-end: ``discovery_s``, the window over the discoveries completed in
it (the one running when the time is up completes and counts, with its
time).
"""
from __future__ import annotations

import time

import numpy as np

from harness.discovery import Discoveries


def write_behind(store) -> None:
    """The control: a store that acknowledges a topology before it is on
    disk, writing each one only when the next arrives (a later flush)."""
    put, pending = store.put, []

    def deferred(key, topo, meta=None):
        pending.append((key, topo, meta))
        if len(pending) > 1:
            put(*pending.pop(0))
        return key

    store.put = deferred


CONTROL = {"store_put": write_behind}


def setup(ctx):
    picks = np.random.default_rng(ctx.seed32).choice(
        ctx.traffic["sample_from"], ctx.traffic["sampled"], replace=False)
    d = Discoveries(ctx, ctx.config, {int(i) for i in picks}).__enter__()
    ctx.mark("store")
    d.once(None)                        # compiles and warms every kernel
    ctx.mark("warm-up")
    return d


def window(ctx, d: Discoveries) -> dict:
    need = max(d.sampled) + 1
    d.tap.reset()
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < ctx.seconds or i < need:
        d.once(i)
        i += 1
    wall = time.perf_counter() - t0
    return {"attempted": i, "failed": d.failed, "wall_s": wall,
            "end_to_end": {"discovery_s": wall / max(d.count, 1)},
            "counters": {"discoveries": d.count,
                         "kernel_calls": d.kernel_calls,
                         "tap_launches": d.tap.launches,
                         "stream_bytes": d.tap.stream_bytes}}


def check(ctx, d: Discoveries) -> dict:
    return d.check(ctx.device)


def close(ctx, d: Discoveries) -> None:
    d.__exit__()
