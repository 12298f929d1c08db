"""The program's own spans (``mt4g.``) on the device's clock.

The program opens ``jax.profiler.TraceAnnotation`` spans named ``mt4g.*``
at its layer boundaries (``repro.tracing``): the discovery, each probe
family, the chase buffer's build, the stream's fill, every kernel launch,
the assembly, the store write; in the serving loop the prefill, and per
token the fetch of the logits, the sampling and the decode dispatch.
``flatten`` keeps them beside the ``bench.`` spans and the device planes,
so ``harness.trace.reduce`` reads its output exactly as before, and
``program`` reduces them:

* for each span name on the thread that holds ``bench.window`` (the
  cells run discovery and serving inline, on that thread) its ``count``,
  total ``ns``, ``self_ns`` (less the ``mt4g.`` spans nested in it),
  ``idle_ns`` (the device's idle time inside its self intervals), and for
  ``mt4g.launch`` the device ns of the probe programs the launches waited
  for;
* ``clock``: the offset that puts device time on the host's clock.  The
  device runs programs in the order the host dispatched them, so the k-th
  probe program belongs to the k-th ``mt4g.launch``, and the k-th model
  program of a serving window (every program but the token batch's cast,
  ``convert_element_type``, which each ``.decode`` dispatches first) to the
  k-th ``mt4g.serve.prefill`` or ``.decode``.
  A program cannot start before its dispatch began, and has ended when the
  host's wait for it ends: the end of its ``mt4g.launch``, or of the first
  ``mt4g.serve.fetch`` after its dispatch.  Each pair bounds the offset
  from both sides.  The offset chosen is the middle of the widest stretch
  that the most pairs allow (all of them, when the bounds meet); it is
  reported with that stretch (``bracket_ns``) and the share of pairs it
  satisfies (``inside``), which a trace that lost a program also shows.

Idle time is attributed after the shift, on the first chip.
"""
from __future__ import annotations

import bisect
import glob
import os

from .trace import (MODULES_LINE, OPS_LINE, WINDOW_SPAN, _device_planes,
                    _label, _line, host_spans, stable_name, union, window)

PREFIX = "mt4g."
PROBES = ("pchase_kernel_batch", "stream_read_kernel", "stream_write_kernel")
TOKEN_CAST = "convert_element_type"
LAUNCH = "mt4g.launch"
DISPATCH = ("mt4g.serve.prefill", "mt4g.serve.decode")
FETCH = "mt4g.serve.fetch"
ENGINE = ("mt4g.discover", "mt4g.assemble")
FAMILY = "mt4g.family."


def flatten(trace_dir: str) -> dict:
    """``harness.trace.flatten`` that also keeps the host's ``mt4g.``
    spans."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"planes": []}
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(("bench.", PREFIX))]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------- intervals
def overlap(ivs, disjoint: list[tuple[int, int]]) -> float:
    """ns that the intervals ``ivs`` share with the sorted, disjoint
    intervals ``disjoint``."""
    starts = [s for s, _ in disjoint]
    total = 0
    for s, e in ivs:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(disjoint) and disjoint[i][0] < e:
            total += max(0, min(e, disjoint[i][1]) - max(s, disjoint[i][0]))
            i += 1
    return total


def intersect(a, b) -> list[tuple[int, int]]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(ivs, lo: int, hi: int) -> list[tuple[int, int]]:
    """``[lo, hi]`` less the intervals ``ivs``."""
    edges = [lo] + [x for iv in union(ivs, lo, hi) for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def best_offset(bounds) -> tuple[float, float, int]:
    """``(lo, hi, n)``: the first widest stretch of points lying in the
    most (``n``) of the closed intervals ``bounds``; an interval whose end
    precedes its start holds none."""
    bounds = [(lo, hi) for lo, hi in bounds if lo <= hi]
    events = sorted([(lo, 0) for lo, _ in bounds]
                    + [(hi, 1) for _, hi in bounds])
    out, depth = (0.0, 0.0, 0), 0
    for i, (x, end) in enumerate(events):
        depth += -1 if end else 1
        if end:
            continue
        nxt = events[i + 1][0]          # an end always follows a start
        if depth > out[2] or (depth == out[2] and nxt - x > out[1] - out[0]):
            out = (x, nxt, depth)
    return out


def self_intervals(spans) -> list[list[tuple[int, int]]]:
    """For each ``(name, start, end)`` span of one thread, sorted by start
    and outer first, the intervals it holds less the spans nested in it."""
    children: list[list[tuple[int, int]]] = [[] for _ in spans]
    stack: list[int] = []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            children[stack[-1]].append((s, min(e, spans[stack[-1]][2])))
        stack.append(i)
    return [complement(children[i], s, e)
            for i, (_, s, e) in enumerate(spans)]


# --------------------------------------------------------------- the window
def _main(flat: dict, lo: int, hi: int) -> list[tuple[str, int, int]]:
    """The ``mt4g.`` spans inside ``[lo, hi]`` of the host thread that
    holds the window, as ``(name, start, end)``, sorted by start, outer
    first."""
    for p in flat["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            evs = line["events"]
            if any(n == WINDOW_SPAN for n, _, _ in evs):
                return sorted(((n, s, s + d) for n, s, d in evs
                               if n.startswith(PREFIX) and lo <= s
                               and s + d <= hi), key=lambda x: (x[1], -x[2]))
    return []


def _programs(plane: dict, probes: bool) -> list[tuple[int, int]]:
    """``(start, end)`` of the programs on one chip, in order: the probe
    kernels, or the model's programs."""
    mods = _line(plane, MODULES_LINE)
    if probes:
        mods = [m for m in mods
                if any(p in stable_name(m[0]) for p in PROBES)]
    else:
        mods = [m for m in mods if TOKEN_CAST not in stable_name(m[0])]
    return sorted((s, s + d) for _, s, d in mods)


def clock(main, programs) -> dict | None:
    """The host-minus-device offset that the pairs of dispatch span and
    program allow (module docstring), or None when none pairs."""
    launches = [s for s in main if s[0] == LAUNCH]
    fetch = [s for s in main if s[0] == FETCH]
    fetch_starts = [s[1] for s in fetch]
    bounds = []
    for (_, start, end), (ps, pe) in zip(
            launches or [s for s in main if s[0] in DISPATCH], programs):
        if not launches:
            j = bisect.bisect_left(fetch_starts, start)
            end = fetch[j][2] if j < len(fetch) else float("inf")
        bounds.append((start - ps, end - pe))
    if not bounds:
        return None
    lo, hi, n = best_offset(bounds)
    hi = lo if hi == float("inf") else hi
    return {"offset_ns": (lo + hi) / 2, "bracket_ns": [lo, hi],
            "width_ns": hi - lo, "pairs": len(bounds),
            "inside": n / len(bounds)}


def _idle(plane: dict, lo: int, hi: int, off: float):
    """The chip's idle intervals in ``[lo, hi]``, on the host's clock."""
    ops = _line(plane, OPS_LINE) or [
        e for line in plane["lines"] if line["name"] != "Steps"
        for e in line["events"]]
    return complement([(s + off, s + d + off) for _, s, d in ops], lo, hi)


def program(flat: dict) -> dict | None:
    """The ``mt4g.`` spans of the traced window, reduced (module
    docstring).  None when the trace holds no window or no device."""
    win, devs = window(flat), _device_planes(flat)
    if win is None or not devs:
        return None
    lo, hi = win
    main = _main(flat, lo, hi)
    launches = [s for s in main if s[0] == LAUNCH]
    programs = _programs(devs[0], probes=bool(launches))
    clk = clock(main, programs)
    idle = _idle(devs[0], lo, hi, clk["offset_ns"] if clk else 0.0)

    stats: dict[str, dict] = {}
    for (name, s, e), own in zip(main, self_intervals(main)):
        st = stats.setdefault(name, {"count": 0, "ns": 0, "self_ns": 0,
                                     "idle_ns": 0})
        st["count"] += 1
        st["ns"] += e - s
        st["self_ns"] += sum(b - a for a, b in own)
        st["idle_ns"] += overlap(own, idle)
    if launches:
        stats[LAUNCH]["device_ns"] = sum(
            pe - ps for _, (ps, pe) in zip(launches, programs))
    return {"spans": stats, "clock": clk, "window_ns": hi - lo,
            "idle_ns": sum(e - s for s, e in idle)}


# ----------------------------------------------------------------- coverage
def coverage(flat: dict, prog: dict | None, scope: str) -> dict | None:
    """How much of the chip's idle time in ``scope`` falls under some
    ``mt4g.`` span of the window's thread, and what is left, by the
    ``mt4g.`` span that closed last before it.  ``scope`` names ``bench.``
    spans (the idle time inside them), or is a label of
    ``harness.trace``'s breakdown such as ``after bench.decode`` (the gaps
    it labels so, found on the device's clock as it finds them, then
    shifted)."""
    win = window(flat)
    if win is None or prog is None or not prog["clock"]:
        return None
    lo, hi = win
    off = prog["clock"]["offset_ns"]
    plane = _device_planes(flat)[0]
    spans = [s for s in host_spans(flat) if s[0] != WINDOW_SPAN]
    if scope.startswith("after "):
        starts = [s[1] for s in spans]
        idle = [(s + off, e + off) for s, e in _idle(plane, lo, hi, 0.0)
                if _label(spans, starts, (s + e) // 2) == scope]
    else:
        inside = union([(s, e) for n, s, e in spans if n == scope], lo, hi)
        idle = intersect(_idle(plane, lo, hi, off), inside)
    main = _main(flat, lo, hi)
    bare = intersect(idle, complement([(s, e) for _, s, e in main], lo, hi))
    total = sum(e - s for s, e in idle)
    ends = sorted((e, n) for n, _, e in main)
    end_ts = [e for e, _ in ends]
    left: dict[str, float] = {}
    for a, b in bare:
        i = bisect.bisect_right(end_ts, a) - 1
        label = f"after {ends[i][1]}" if i >= 0 else "before any"
        left[label] = left.get(label, 0) + b - a
    covered = total - sum(left.values())
    return {"idle_ns": total, "covered_ns": covered,
            "share": covered / total if total else None,
            "left": sorted(left.items(), key=lambda kv: -kv[1])[:8]}


# ------------------------------------------------------------------ metrics
def metrics(prog: dict | None, counters: dict) -> dict:
    """The six per-layer numbers these spans give, those the window holds
    something for: per discovery or per decode step of the window."""
    if prog is None:
        return {}
    sp = prog["spans"]
    out = {}
    n = counters.get("discoveries")
    if n:
        if LAUNCH in sp:
            # the device idle inside a launch: its span less the device
            # time of what it waited for (its program, or the stream's
            # fill before the first stream launch)
            out["launch_overhead_us"] = (sp[LAUNCH]["idle_ns"]
                                         / sp[LAUNCH]["count"] * 1e-3)
        for key, name in (("chase_build_ms_per_discovery", "mt4g.chase.build"),
                          ("store_put_ms_per_discovery", "mt4g.store.put")):
            if name in sp:
                out[key] = sp[name]["ns"] / n * 1e-6
        own = [v["self_ns"] for k, v in sp.items()
               if k in ENGINE or k.startswith(FAMILY)]
        if own:
            out["engine_self_ms_per_discovery"] = sum(own) / n * 1e-6
    steps = counters.get("decode_steps")
    if steps:
        if "mt4g.serve.sample" in sp:
            out["sample_ms_per_step"] = (sp["mt4g.serve.sample"]["ns"]
                                         / steps * 1e-6)
        if FETCH in sp:
            out["fetch_idle_ms_per_step"] = (sp[FETCH]["idle_ns"]
                                             / steps * 1e-6)
    return out
