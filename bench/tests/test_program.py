"""The reduction of the program's ``mt4g.`` spans (``harness.program``) on
hand-made traces whose device clock runs a known distance from the
host's."""
import pytest

from harness import program as prog
from harness.trace import breakdown, reduce

B = 10_000_000          # host clock at the window's start
K = 1000


def _flat(host_events, device_programs, shift):
    """A trace: the host's events on one thread, and one op per program
    on the device, whose clock reads ``shift`` ns less than the host's."""
    mods = [[f"jit_{name}(7)", B + s - shift, e - s]
            for name, s, e in device_programs]
    ops = [[f"%{name}.1 = s32[2] custom-call()", B + s - shift, e - s]
           for name, s, e in device_programs]
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        [n, B + s, e - s] for n, s, e in host_events]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops}]}
    return {"planes": [host, dev]}


DISCOVERY = [
    ("bench.window", 0, 10_000 * K),
    ("bench.discovery", 100 * K, 9_000 * K),
    ("mt4g.discover", 200 * K, 8_900 * K),
    ("mt4g.family.latency", 300 * K, 6_000 * K),
    ("mt4g.chase.build", 400 * K, 1_000 * K),
    ("mt4g.launch", 1_100 * K, 1_600 * K),
    ("mt4g.launch", 2_200 * K, 3_200 * K),
    ("mt4g.launch", 3_300 * K, 4_300 * K),
    ("mt4g.assemble", 6_100 * K, 6_500 * K),
    ("mt4g.store.put", 8_000 * K, 8_800 * K),
]
# each launch's program on the host's clock: the first starts 10 us after
# its span opened, the second ends 10 us before its span closed; the first
# is shorter than the others, as a short chain is
PROBE_PROGRAMS = [("pchase_kernel_batch", 1_110 * K, 1_500 * K),
                  ("pchase_kernel_batch", 2_300 * K, 3_190 * K),
                  ("stream_read_kernel", 3_400 * K, 4_200 * K)]
SHIFT = 1_500 * K       # the device's clock 1.5 ms behind the host's


def test_best_offset_finds_the_most_bounds():
    assert prog.best_offset([(0, 5), (3, 10), (20, 30)]) == (3, 5, 2)
    assert prog.best_offset([(0, 5), (5, 9)]) == (5, 5, 2)
    assert prog.best_offset([(0, 1), (4, 9)]) == (4, 9, 1)   # the widest
    assert prog.best_offset([(5, 0), (1, 2)]) == (1, 2, 1)


def test_offset_bracket_recovers_a_known_shift():
    red = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS, SHIFT))
    clk = red["clock"]
    assert clk["bracket_ns"] == [SHIFT - 10 * K, SHIFT + 10 * K]
    assert clk["offset_ns"] == SHIFT and clk["width_ns"] == 20 * K
    assert clk["inside"] == 1.0 and clk["pairs"] == 3


def test_a_lost_program_shows_as_pairs_outside_their_span():
    clk = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS[1:], SHIFT))["clock"]
    # the first launch, 500 us long, cannot hold the 890 us program it is
    # now paired with
    assert clk["pairs"] == 2 and clk["inside"] == 0.5


def test_self_time_and_idle_under_each_span():
    sp = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS, SHIFT))["spans"]
    assert sp["mt4g.discover"]["self_ns"] == 1_800 * K
    assert sp["mt4g.family.latency"]["self_ns"] == 2_600 * K
    assert sp["mt4g.launch"]["count"] == 3
    assert sp["mt4g.launch"]["ns"] == sp["mt4g.launch"]["self_ns"] \
        == 2_500 * K
    # after the shift, the device is busy only inside the launches
    assert sp["mt4g.chase.build"]["idle_ns"] == 600 * K
    assert sp["mt4g.family.latency"]["idle_ns"] == 2_600 * K
    assert sp["mt4g.discover"]["idle_ns"] == 1_800 * K
    assert sp["mt4g.launch"]["idle_ns"] == (110 + 110 + 200) * K
    assert sp["mt4g.launch"]["device_ns"] == (390 + 890 + 800) * K


def test_idle_does_not_depend_on_the_device_clock():
    unshifted = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS, 0))["spans"]
    shifted = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS, SHIFT))["spans"]
    for name in ("mt4g.launch", "mt4g.chase.build", "mt4g.discover"):
        assert unshifted[name]["idle_ns"] == shifted[name]["idle_ns"]


def test_discovery_metrics():
    red = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS, SHIFT))
    m = prog.metrics(red, {"discoveries": 1})
    assert m == pytest.approx({
        "launch_overhead_us": 140.0,          # idle in the launches / 3
        "chase_build_ms_per_discovery": 0.6,
        "store_put_ms_per_discovery": 0.8,
        "engine_self_ms_per_discovery": 1.8 + 2.6 + 0.4})
    assert prog.metrics(red, {}) == {} and prog.metrics(None, {}) == {}


def test_a_fill_the_launch_waited_for_is_not_overhead():
    fill = ("broadcast_in_dim", 3_310 * K, 3_390 * K)  # inside the 3rd launch
    red = prog.program(_flat(DISCOVERY, PROBE_PROGRAMS + [fill], SHIFT))
    assert red["clock"]["offset_ns"] == SHIFT       # the fill is not paired
    assert red["spans"]["mt4g.launch"]["device_ns"] == (390 + 890 + 800) * K
    assert prog.metrics(red, {"discoveries": 1})["launch_overhead_us"] == \
        pytest.approx((420 - 80) / 3)


def test_discovery_coverage_names_what_is_left():
    flat = _flat(DISCOVERY, PROBE_PROGRAMS, SHIFT)
    cov = prog.coverage(flat, prog.program(flat), "bench.discovery")
    assert cov["idle_ns"] == (8_900 - 2_080) * K
    assert cov["covered_ns"] == cov["idle_ns"] - 200 * K
    assert sorted(cov["left"]) == [("after mt4g.discover", 100 * K),
                                   ("before any", 100 * K)]


SERVE = [
    ("bench.window", 0, 5_000 * K),
    ("mt4g.serve.prefill", 100 * K, 300 * K),
    ("bench.prefill", 150 * K, 290 * K),
    ("mt4g.serve.fetch", 300 * K, 1_300 * K),
    ("mt4g.serve.sample", 1_300 * K, 1_500 * K),
    ("mt4g.serve.decode", 1_500 * K, 1_600 * K),
    ("bench.decode", 1_520 * K, 1_590 * K),
    ("mt4g.serve.fetch", 1_600 * K, 2_600 * K),
    ("mt4g.serve.sample", 2_600 * K, 2_800 * K),
    ("mt4g.serve.decode", 2_800 * K, 2_900 * K),
    ("bench.decode", 2_820 * K, 2_890 * K),
]
SERVE_PROGRAMS = [("_lambda", 200 * K, 1_200 * K),
                  ("_lambda", 1_550 * K, 2_550 * K),
                  ("_lambda", 2_850 * K, 3_600 * K)]   # never fetched
SERVE_SHIFT = -700 * K  # the device's clock 0.7 ms ahead of the host's


def test_serving_clock_fetch_idle_and_sampling():
    red = prog.program(_flat(SERVE, SERVE_PROGRAMS, SERVE_SHIFT))
    clk = red["clock"]
    assert clk["bracket_ns"] == [SERVE_SHIFT - 50 * K, SERVE_SHIFT + 50 * K]
    assert clk["offset_ns"] == SERVE_SHIFT and clk["pairs"] == 3
    sp = red["spans"]
    assert sp["mt4g.serve.fetch"]["idle_ns"] == (100 + 50) * K
    assert sp["mt4g.serve.decode"]["count"] == 2
    m = prog.metrics(red, {"decode_steps": 2})
    assert m == pytest.approx({"sample_ms_per_step": 0.2,
                               "fetch_idle_ms_per_step": 0.075})


def test_serving_coverage_of_the_gaps_labelled_after_decode():
    flat = _flat(SERVE, SERVE_PROGRAMS, SERVE_SHIFT)
    cov = prog.coverage(flat, prog.program(flat), "after bench.decode")
    # on the device's clock the gaps after a decode call read 350, 300 and
    # 700 us; shifted, the first two lie under the serving loop's spans
    assert cov["idle_ns"] == 1_350 * K
    assert cov["covered_ns"] == 650 * K
    assert cov["left"] == [("after mt4g.serve.decode", 700 * K)]


@pytest.mark.parametrize("host, programs, shift", [
    (DISCOVERY, PROBE_PROGRAMS, SHIFT),
    (SERVE, SERVE_PROGRAMS, SERVE_SHIFT)])
def test_bench_reduction_ignores_the_program_spans(host, programs, shift):
    with_spans = _flat(host, programs, shift)
    without = _flat([e for e in host if not e[0].startswith("mt4g.")],
                    programs, shift)
    a, b = reduce(with_spans), reduce(without)
    for key in ("by_span", "gaps", "busy_ns", "modules", "ops_ns"):
        assert a[key] == b[key]
    assert breakdown(a) == breakdown(b)


def test_no_window_or_no_device_gives_nothing():
    flat = _flat(DISCOVERY, PROBE_PROGRAMS, SHIFT)
    assert prog.program({"planes": flat["planes"][:1]}) is None
    assert prog.program({"planes": flat["planes"][1:]}) is None
    assert prog.coverage(flat, None, "bench.discovery") is None


def test_flatten_keeps_program_and_bench_spans(tmp_path):
    import jax

    from harness.trace import flatten as bench_flatten

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("mt4g.launch"):
                jax.numpy.ones(8).block_until_ready()
    names = {e[0] for p in prog.flatten(str(tmp_path))["planes"]
             for line in p["lines"] for e in line["events"]}
    assert {"bench.window", "mt4g.launch"} <= names
    bench = {e[0] for p in bench_flatten(str(tmp_path))["planes"]
             for line in p["lines"] for e in line["events"]}
    assert "mt4g.launch" not in bench and "bench.window" in bench
