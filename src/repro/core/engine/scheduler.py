"""Dependency-aware work-item scheduler for the probe engine.

Discovery decomposes into (memory space × probe family) work items with a
small dependency DAG (line size needs size + fetch granularity; sharing
needs every partner's size; ...).  The scheduler runs them inline on the
calling thread in dependency order, one round of ready items at a time;
``engine/fusion.py`` is the one alternative schedule (fused rounds).

Correctness does not depend on scheduling: probe sample streams are keyed
by request (see ``simulate._KeyedSampler``), so any execution order
produces identical results.  The per-family wall times are accumulated
into the same ``DiscoveryTimings`` buckets the legacy sequential loop
reports (a sum of item durations, matching the paper's §V-A per-family
accounting); each item runs in the profiler span
``mt4g.family.<family>`` whose interval is the one its bucket gets
(``run_item``, shared with ``fusion.run_fused``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Hashable

from ...tracing import span
from ..errors import TransientRunnerError

__all__ = ["WorkItem", "ScheduleResult", "run_work_items", "check_items",
           "run_item"]


@dataclass(frozen=True)
class WorkItem:
    """One schedulable unit of discovery work.

    ``fn`` receives the results-so-far mapping (keyed like ``key``) and
    returns the item's result; it must only read keys listed in ``deps``.
    """

    key: Hashable
    fn: Callable[[dict], Any]
    deps: tuple = ()
    family: str = ""                # DiscoveryTimings bucket


@dataclass
class ScheduleResult:
    """Scheduler output: item results, completion order, and the
    fault-tolerance tallies (transient retries spent, items degraded)."""

    results: dict = field(default_factory=dict)
    order: list = field(default_factory=list)    # completion order
    retries: int = 0                             # transient retries spent
    degraded: list = field(default_factory=list)  # keys past the budget


def check_items(items: list[WorkItem]) -> dict:
    """Validate keys/deps; returns the key->item map (shared with fusion)."""
    by_key = {it.key: it for it in items}
    if len(by_key) != len(items):
        raise ValueError("duplicate work-item keys")
    for it in items:
        unknown = [d for d in it.deps if d not in by_key]
        if unknown:
            raise ValueError(f"{it.key}: unknown deps {unknown}")
    return by_key


def run_item(it: WorkItem, results: dict) -> tuple[Any, float]:
    """``it.fn(results)`` inside the span ``mt4g.family.<family>``: returns
    the value and its wall seconds, read just inside the span, so the
    item's ``DiscoveryTimings`` bucket gets the span's interval."""
    with span(f"mt4g.family.{it.family}"):
        t0 = time.perf_counter()
        value = it.fn(results)
        return value, time.perf_counter() - t0


def run_work_items(items: list[WorkItem], *, timings=None, fuser=None,
                   resilience=None, on_exhausted=None,
                   on_item_done=None) -> ScheduleResult:
    """Execute ``items`` respecting dependencies; returns results + order.

    Items run inline on the calling thread, in rounds: each round runs, in
    list order, every item whose dependencies have completed.  Results do
    not depend on the order (request-keyed sampling); concurrency, where a
    runner has any, lives below the schedule — in the worker processes of
    a ``parallel=`` pool, or in a fused round's one batched dispatch.

    ``fuser`` (a ``fusion.FusionDispatcher``) switches to round-based
    cross-family batch fusion: ready items run concurrently but every
    probe dispatch is coalesced and executed serially by the coordinator —
    see ``engine/fusion.py``.

    Fault tolerance (``resilience``, an ``errors.Resilience``): an item
    raising ``TransientRunnerError`` is re-attempted up to
    ``resilience.max_retries`` times with capped exponential backoff.  Past
    the budget, if ``resilience.degrade`` and ``on_exhausted`` is given,
    ``on_exhausted(item, exc, attempts)`` supplies the item's stand-in
    result (recorded in ``ScheduleResult.degraded``) and scheduling
    continues; otherwise the error propagates as before.  Non-transient
    exceptions always propagate — a deterministic bug must not be retried
    into a topology.  ``on_item_done(key)`` fires after each item lands
    (the checkpoint write-through hook), on the calling thread.

    Raises on unknown dependencies or cycles (both indicate a registry bug,
    not a runtime condition worth limping through).
    """
    if fuser is not None:
        from .fusion import run_fused

        return run_fused(items, fuser, timings=timings,
                         resilience=resilience, on_exhausted=on_exhausted,
                         on_item_done=on_item_done)

    out = ScheduleResult()
    pending = dict(check_items(items))

    def attempt(it: WorkItem):
        """``run_item`` under the resilience policy: retry transients with
        capped backoff, then degrade (via ``on_exhausted``) or re-raise."""
        attempts = 0
        while True:
            try:
                value, dt = run_item(it, out.results)
                if timings is not None and it.family:
                    timings.add(it.family, dt)
                return value
            except TransientRunnerError as exc:
                if resilience is None:
                    raise
                if attempts >= resilience.max_retries:
                    if resilience.degrade and on_exhausted is not None:
                        out.degraded.append(it.key)
                        return on_exhausted(it, exc, attempts + 1)
                    raise
                resilience.sleep(resilience.backoff(attempts))
                attempts += 1
                out.retries += 1

    while pending:
        ready_now = [it for it in pending.values()
                     if all(d in out.results for d in it.deps)]
        if not ready_now:
            raise ValueError("dependency cycle among work items: "
                             f"{sorted(map(str, pending))}")
        for it in ready_now:
            out.results[it.key] = attempt(it)
            out.order.append(it.key)
            del pending[it.key]
            if on_item_done is not None:
                on_item_done(it.key)
    return out
