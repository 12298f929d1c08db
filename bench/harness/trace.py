"""From a profiler trace to the numbers the per-layer metrics read.

A trace is first flattened to plain data (``flatten``): for each plane its
lines, for each line its events as ``[name, start_ns, duration_ns]``.  That
form is what ``reduce`` works on, and what the tests record.  Device planes
are named ``/device:TPU:<n>``; the host's plane holds the spans that the
benchmark's own files open with ``jax.profiler.TraceAnnotation`` (names
starting ``bench.``), the window among them (``bench.window``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def stable_name(name: str) -> str:
    """``jit_stream_read_kernel(1234)`` -> ``jit_stream_read_kernel``;
    ``%fusion.12 = bf16[...] fusion(...)`` (an op as the TPU trace names
    it, with its HLO) -> ``fusion``: names that survive a recompile."""
    name = name.split(" = ", 1)[0].lstrip("%")
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def flatten(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain data, keeping
    the device planes and the host spans of this benchmark."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"planes": []}
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _device_planes(flat: dict) -> list[dict]:
    return [p for p in flat["planes"] if p["name"].startswith("/device:TPU")]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(flat: dict) -> list[tuple[str, int, int]]:
    """``(name, start_ns, end_ns)`` of every ``bench.`` span on the host."""
    out = []
    for p in flat["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"]
                    if n.startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def window(flat: dict) -> tuple[int, int] | None:
    spans = [s for s in host_spans(flat) if s[0] == WINDOW_SPAN]
    return (spans[0][1], spans[0][2]) if spans else None


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    merged: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(events, lo: int, hi: int):
    for n, s, d in events:
        c = min(s + d, hi) - max(s, lo)
        if c > 0:
            yield n, s, c


def _self_times(events, lo: int, hi: int):
    """``(name, self ns)`` of each event clipped to ``[lo, hi]``: its time
    less that of the events nested in it on the same line (a ``while``
    holds the operations of its body)."""
    evs = sorted(((max(s, lo), min(s + d, hi), n) for n, s, d in events),
                 key=lambda e: (e[0], -e[1]))
    own: list[list] = []
    stack: list[int] = []
    for s, e, n in evs:
        if e <= s:
            continue
        while stack and own[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]][1] -= min(e, own[stack[-1]][2]) - s
        own.append([n, e - s, e])
        stack.append(len(own) - 1)
    return [(n, t) for n, t, _ in own]


def reduce(flat: dict, top: int = 10) -> dict | None:
    """The traced window's device time: busy and idle, self time by operation
    and by program (stable names), program time by the ``bench.`` span
    most recently opened on the host when the program ended (the call that
    launched it, where the host waits for each result; the end, not the
    start, because the device's clock runs a millisecond or two apart from
    the host's and programs last longer than that), and the
    longest idle gaps, each named by what the host was doing at its
    middle (``_label``).

    Busy time is averaged over the device planes (chips).  Returns None
    when the trace holds no window or no device."""
    win = window(flat)
    devs = _device_planes(flat)
    if win is None or not devs:
        return None
    lo, hi = win
    busy, ops, modules, by_span, gaps = 0, {}, {}, {}, []
    spans = [s for s in host_spans(flat) if s[0] != WINDOW_SPAN]
    starts = [s[1] for s in spans]
    for plane in devs:
        op_events = _line(plane, OPS_LINE)
        if not op_events:
            op_events = [e for line in plane["lines"]
                         if line["name"] != "Steps" for e in line["events"]]
        ivs = union([(s, s + d) for _, s, d in op_events], lo, hi)
        busy += sum(e - s for s, e in ivs)
        for n, c in _self_times(op_events, lo, hi):
            k = stable_name(n)
            ops[k] = ops.get(k, 0) + c
        for n, start, c in _clip(_line(plane, MODULES_LINE), lo, hi):
            k = stable_name(n)
            t, cnt = modules.get(k, (0, 0))
            modules[k] = (t + c, cnt + 1)
            i = bisect.bisect_right(starts, start + c) - 1
            k = spans[i][0] if i >= 0 else "host"
            t, cnt = by_span.get(k, (0, 0))
            by_span[k] = (t + c, cnt + 1)
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(spans, starts, (s + e) // 2), e - s))
    n = len(devs)
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_ns": hi - lo,
        "busy_ns": busy / n,
        "ops_ns": {k: v / n for k, v in ops.items()},
        "modules": {k: {"ns": t / n, "count": c / n}
                    for k, (t, c) in modules.items()},
        "by_span": {k: {"ns": t / n, "count": c / n}
                    for k, (t, c) in by_span.items()},
        "gaps": gaps,
        "device_ops": sorted(((k, v / n) for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
    }


def _label(spans, starts, t: int) -> str:
    """What the host was doing at ``t``: the ``bench.`` span most recently
    opened, or ``after <span>`` once it has closed; ``host`` before any."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0:
        return "host"
    name, _, end = spans[i]
    return name if end >= t else f"after {name}"


def module_ns(red: dict, *needles: str) -> tuple[float, float]:
    """(device ns, launches) of the programs whose stable name holds any
    of ``needles``."""
    t = c = 0.0
    for name, m in red["modules"].items():
        if any(x in name for x in needles):
            t += m["ns"]
            c += m["count"]
    return t, c


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time and the longest idle gaps by what the host was doing, seconds."""
    by_label: dict[str, float] = {}
    for label, ns in red["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + ns
    return {
        "device_ops": [[k, v * 1e-9] for k, v in red["device_ops"][:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in
                      sorted(by_label.items(), key=lambda kv: -kv[1])[:top]],
    }
