"""The program's profiler spans (``repro.tracing``), read back from the
``.xplane.pb`` that ``jax.profiler.trace`` writes, on the CPU.

The chip path runs ``TpuRunner``'s own chase, stream and launch code with
the probe kernels in Pallas' interpreter; only the TPU check is lifted.
"""
import glob
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.kernels.pchase_probe as pchase_probe
import repro.kernels.stream_probe as stream_probe
from repro.configs import get_config
from repro.core import discover_pallas, discover_sim, make_h100_like
from repro.core.engine.store import TopologyStore
from repro.core.probes import TpuRunner
from repro.models import get_model
from repro.core.engine.scheduler import WorkItem, run_item
from repro.serve import Engine, ServeConfig

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


def _spans(trace_dir) -> list[tuple[str, int, int, str]]:
    """``(name, start_ns, end_ns, thread)`` of every ``mt4g.`` span."""
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, int(e.start_ns),
                     int(e.start_ns) + int(e.duration_ns), line.name)
                    for e in line.events if e.name.startswith("mt4g.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2] \
        and inner[3] == outer[3]


class _CpuChip(TpuRunner):
    """``TpuRunner`` on the CPU: no TPU check, a 4 MiB stream."""

    STREAM_BYTES = 4 << 20
    STREAM_BLOCK_ROWS = 256

    def __init__(self):                     # noqa: D401 — no TPU check
        self.device = jax.devices()[0]
        self.device_kind = "cpu-stand-in"
        self.info = SimpleNamespace(vmem_capacity_bytes=128 << 20,
                                    smem_capacity_bytes=1 << 20, num_cores=1)
        self._rng = np.random.default_rng(0)
        self._chase = {}
        self._stream = None
        self.kernel_calls = 0


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The probe kernels in Pallas' interpreter, where ``TpuRunner`` looks
    them up at each call."""
    for mod, name in ((pchase_probe, "pchase_kernel_batch"),
                      (stream_probe, "stream_read_kernel"),
                      (stream_probe, "stream_write_kernel")):
        fn = getattr(mod, name)
        monkeypatch.setattr(mod, name,
                            lambda *a, _fn=fn, **kw: _fn(*a, interpret=True,
                                                         **kw))


def test_chip_path_discovery_spans_nest(tmp_path, interpreted_kernels):
    store = TopologyStore(str(tmp_path / "store"))
    discover_pallas(runner=_CpuChip(), n_samples=9, store=store,
                    refresh=True)                   # compiles the kernels
    runner = _CpuChip()
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, timings = discover_pallas(runner=runner, n_samples=9,
                                     store=store, refresh=True)
    spans = _spans(tmp_path / "trace")

    top, = _named(spans, "mt4g.discover")
    families = {s[0]: s for s in spans if s[0].startswith("mt4g.family.")}
    assert set(families) == {"mt4g.family.latency", "mt4g.family.bandwidth"}
    assert all(_inside(f, top) for f in families.values())
    lat = families["mt4g.family.latency"]
    bw = families["mt4g.family.bandwidth"]

    launches = _named(spans, "mt4g.launch")
    assert runner.kernel_calls == 88
    assert len(launches) == runner.kernel_calls
    assert all(_inside(s, lat) or _inside(s, bw) for s in launches)
    assert sum(_inside(s, bw) for s in launches) == 2 * (runner.REPS + 1)

    build, = _named(spans, "mt4g.chase.build")
    fill, = _named(spans, "mt4g.stream.fill")
    assert _inside(build, lat) and _inside(fill, bw)
    assert not any(_inside(s, build) or _inside(s, fill) for s in launches)

    assemble, = _named(spans, "mt4g.assemble")
    put, = _named(spans, "mt4g.store.put")
    assert _inside(assemble, top) and _inside(put, top)
    assert max(lat[2], bw[2]) <= assemble[1] <= assemble[2] <= put[1]

    # each bucket is the interval of its family's span
    assert set(timings.per_family) == {"latency", "bandwidth"}
    for fam, s in (("latency", lat), ("bandwidth", bw)):
        assert 0 < timings.per_family[fam] <= (s[2] - s[1]) * 1e-9 + 1e-4


def test_runner_init_span(tmp_path):
    """``TpuRunner()`` refuses the CPU inside its ``mt4g.runner.init``."""
    with jax.profiler.trace(str(tmp_path)):
        with pytest.raises(RuntimeError, match="measures a TPU"):
            TpuRunner()
    assert len(_named(_spans(tmp_path), "mt4g.runner.init")) == 1


@pytest.mark.parametrize("fuse", [False, True])
def test_family_spans_fill_timings(tmp_path, fuse):
    """Scheduled and fused items alike: one ``mt4g.family.<f>`` span per
    work item, and every bucket of ``DiscoveryTimings`` filled."""
    with jax.profiler.trace(str(tmp_path)):
        _, timings = discover_sim(make_h100_like(), n_samples=5,
                                  elements=["L1"], fuse=fuse)
    spans = _spans(tmp_path)
    names = {s[0] for s in spans if s[0].startswith("mt4g.family.")}
    assert names == {f"mt4g.family.{f}" for f in timings.per_family}
    assert all(v > 0 for v in timings.per_family.values())
    top, = _named(spans, "mt4g.discover")
    assert _named(spans, "mt4g.assemble")
    if not fuse:            # fused items run on threads of their own
        assert all(_inside(s, top) for s in spans if s is not top)


def test_generate_batch_spans(tmp_path):
    cfg = get_config("internlm2-1.8b").smoke().replace(dtype="float32")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    eng = Engine(model, params, ServeConfig(max_len=32, slots=2))
    prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % cfg.vocab_size
    want = eng.generate_batch(prompts, max_new=4)       # compiles
    before = eng.steps
    with jax.profiler.trace(str(tmp_path)):
        got = eng.generate_batch(prompts, max_new=4)
    np.testing.assert_array_equal(got, want)
    assert eng.steps - before == 4
    spans = _spans(tmp_path)
    counts = {n: len(_named(spans, n)) for n in (
        "mt4g.serve.prefill", "mt4g.serve.fetch", "mt4g.serve.sample",
        "mt4g.serve.decode")}
    assert counts == {"mt4g.serve.prefill": 1, "mt4g.serve.fetch": 4,
                      "mt4g.serve.sample": 4, "mt4g.serve.decode": 4}
    # on one thread: the first token is selected and read before the
    # first decode; then each step dispatches its decode and the next
    # selection before it reads the token it decoded, one step behind;
    # the last decode's logits go unsampled
    steps = [s[0] for s in spans if s[0] != "mt4g.serve.prefill"]
    decode, sample, fetch = ("mt4g.serve.decode", "mt4g.serve.sample",
                             "mt4g.serve.fetch")
    assert steps == [sample, fetch, decode, sample,
                     decode, sample, fetch, decode, sample, fetch,
                     decode, fetch]


def test_run_item_times_its_span(tmp_path):
    item = WorkItem(key="k", family="x",
                    fn=lambda _r: np.linalg.svd(np.ones((64, 64)))[1][0])
    with jax.profiler.trace(str(tmp_path)):
        value, seconds = run_item(item, {})
    s, = _spans(tmp_path)
    assert s[0] == "mt4g.family.x" and value == pytest.approx(64.0)
    assert 0 < seconds <= (s[2] - s[1]) * 1e-9 + 1e-4


def test_core_import_leaves_jax_out():
    """The sim and host paths never import JAX, and without it a span is
    a null context."""
    code = ("import sys; import repro.core; from repro.tracing import span; "
            "from repro.core import discover_sim, make_h100_like; "
            "discover_sim(make_h100_like(), n_samples=3, elements=['L1']); "
            "assert span('mt4g.x').__enter__() is None; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    env = {**os.environ, "PYTHONPATH": SRC}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
