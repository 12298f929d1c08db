"""The reduction from a trace to busy, idle, kernel and span times."""
import pytest

from harness.trace import breakdown, module_ns, reduce, stable_name, union


def _flat():
    """A hand-made trace: a 1000 ns window; on the device two programs
    (a kernel launched from a discovery span, a decode step launched from
    a decode span) whose operations overlap in part."""
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ["bench.window", 1000, 1000],
            ["bench.discovery", 1000, 300],
            ["bench.decode", 1500, 50],
        ]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_stream_read_kernel(123)", 1100, 200],
            ["jit__lambda(77)", 1600, 300],
        ]},
        {"name": "XLA Ops", "events": [
            ["stream_read.3", 1100, 150],
            ["fusion.1", 1200, 100],        # overlaps the kernel op
            ["fusion.2", 1600, 100],
            ["copy.5", 1800, 300],          # runs past the window's end
            ["fusion.9", 500, 100],         # before the window
        ]},
    ]}
    return {"planes": [host, dev]}


def test_stable_names():
    assert stable_name("jit_stream_read_kernel(1234)") == \
        "jit_stream_read_kernel"
    assert stable_name("fusion.12") == "fusion"
    assert stable_name("copy-start.3") == "copy-start"


def test_union_merges_and_clips():
    assert union([(0, 5), (3, 8), (10, 12)], 2, 11) == [(2, 8), (10, 11)]


def test_busy_idle_and_time_by_name():
    red = reduce(_flat())
    assert red["window_ns"] == 1000
    # busy: [1100, 1300) + [1600, 1700) + [1800, 2000) = 500 ns
    assert red["busy_ns"] == 500
    # the 50 ns in which two operations overlap count once
    assert red["ops_ns"] == {"stream_read": 100, "fusion": 200, "copy": 200}
    assert sum(red["ops_ns"].values()) == red["busy_ns"]
    assert module_ns(red, "stream_read_kernel") == (200, 1)
    assert red["by_span"] == {"bench.discovery": {"ns": 200, "count": 1},
                              "bench.decode": {"ns": 300, "count": 1}}


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    red = reduce(_flat())
    gaps = sorted(red["gaps"], key=lambda g: g[1])
    # [1000,1100) inside the discovery span; [1300,1600) after it closed;
    # [1700,1800) after the decode call returned.
    assert gaps == [("bench.discovery", 100), ("after bench.decode", 100),
                    ("after bench.discovery", 300)]
    b = breakdown(red)
    assert b["idle_gaps"][0] == ["after bench.discovery",
                                 pytest.approx(300e-9)]
    assert b["device_ops"][0][0] in ("fusion", "copy")


def test_no_window_or_no_device_gives_nothing():
    flat = _flat()
    assert reduce({"planes": flat["planes"][1:]}) is None
    assert reduce({"planes": flat["planes"][:1]}) is None


def test_recorded_discovery_on_the_chip():
    """One ``discover_pallas`` traced on a TPU v5e chip, with
    the window put around its span.  Counted by hand from the program:
    2 warm chains + 74 timed chains, and 6 launches of each stream."""
    import json
    import os

    from harness.common import BENCH

    with open(os.path.join(BENCH, "tests", "data",
                           "discovery_trace.json")) as f:
        red = reduce(json.load(f))
    assert module_ns(red, "pchase_kernel_batch")[1] == 76
    assert module_ns(red, "stream_read_kernel")[1] == 6
    assert module_ns(red, "stream_write_kernel")[1] == 6
    assert 0 < red["busy_ns"] < red["window_ns"]
    chase, _ = module_ns(red, "pchase_kernel_batch")
    # the chase takes most of the device time, as PERF.md says
    assert chase / red["busy_ns"] > 0.7
    assert red["by_span"]["bench.discovery"]["count"] >= 88
    assert breakdown(red)["device_ops"][0][0] == "pchase_kernel_batch"


def test_nested_operations_count_their_own_time_once():
    from harness.trace import _self_times

    events = [["while", 0, 100], ["fusion.1", 10, 30], ["fusion.2", 50, 20],
              ["copy", 120, 10]]
    assert sorted(_self_times(events, 0, 1000)) == [
        ("copy", 10), ("fusion.1", 30), ("fusion.2", 20), ("while", 50)]
