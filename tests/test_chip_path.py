"""The chip path of discovery, checked without a chip.

* It refuses a CPU: ``TpuRunner`` and ``discover_pallas()`` raise under
  ``JAX_PLATFORMS=cpu``, and a kernel wrapper called without naming an
  interpreter raises instead of interpreting.
* Its assembly — named ``pallas-tpu:<device_kind>``, clock domain ``ns``,
  API-provenance VMEM/SMEM, a store hit on the second request — runs on a
  stand-in with ``TpuRunner``'s surface and canned measurements.
* Its chase buffer lays the cycle out at the requested stride.
* ``HostRunner`` and pool workers stay off the accelerator.
* The compile cache goes where ``enable_compile_cache`` says.
"""
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import discover_pallas
from repro.core.discover import (pallas_request_descriptor,
                                 tpu_request_descriptor)
from repro.core.engine.store import TopologyStore, request_key
from repro.core.probes import (HostRunner, PallasRunner, TpuRunner,
                               make_pallas_model)
from repro.core.probes.tpu_runner import strided_cycle
from repro.kernels import ops
from repro.kernels.pchase_probe import pchase_reference
from repro.serve.topology_service import TopologyService

KIND = "TPU v5 lite"


# ------------------------------------------------------------ no fallback
def test_jax_here_is_cpu_only():
    assert jax.devices()[0].platform == "cpu"


def test_tpu_runner_raises_on_cpu():
    with pytest.raises(RuntimeError, match="TPU"):
        TpuRunner()


def test_discover_pallas_chip_path_raises_on_cpu(tmp_path):
    store = TopologyStore(str(tmp_path))
    with pytest.raises(RuntimeError, match="TPU"):
        discover_pallas(store=store)
    assert store.keys() == []


@pytest.mark.parametrize("call", [
    lambda: ops.pchase(jnp.zeros(128, jnp.int32), iters=4),
    lambda: ops.pchase_batch(jnp.zeros((2, 128), jnp.int32), [4, 4]),
    lambda: ops.stream_read(jnp.ones((16, 128), jnp.float32), block_rows=8),
    lambda: ops.stream_write(jnp.ones((16, 128), jnp.float32), block_rows=8),
], ids=["pchase", "pchase_batch", "stream_read", "stream_write"])
def test_kernel_wrappers_never_interpret_by_default(call):
    with pytest.raises(ValueError, match="interpret"):
        call()


def test_modeled_runner_refuses_the_chip_path():
    with pytest.raises(ValueError, match="interpreter"):
        PallasRunner(make_pallas_model(), interpret=False)
    runner = PallasRunner(make_pallas_model(), interpret=True)
    with pytest.raises(ValueError, match="interpret"):
        discover_pallas(runner=runner)          # interpret left out


def test_chip_and_interpret_keys_never_collide():
    chip = tpu_request_descriptor(KIND, 9)
    interp = pallas_request_descriptor(make_pallas_model(), 9, None)
    assert chip["backend"] == f"pallas-tpu:{KIND}"
    assert interp["backend"].startswith("pallas-interp:")
    assert request_key(chip) != request_key(interp)
    assert request_key(chip) != request_key(tpu_request_descriptor("TPU v4",
                                                                   9))


# ------------------------------------------------ chip assembly, stand-in
class _StandInChip(TpuRunner):
    """``TpuRunner``'s discovery surface with canned measurements."""

    def __init__(self):                  # no device check: nothing runs
        self.device_kind = KIND
        self.info = SimpleNamespace(vmem_capacity_bytes=128 << 20,
                                    smem_capacity_bytes=1 << 20, num_cores=1)
        self.kernel_calls = 0

    def pchase(self, space, array_bytes, stride, n_samples):
        assert space == "DeviceMemory"
        self.kernel_calls += 1
        return np.linspace(400.0, 420.0, int(n_samples))

    def bandwidth(self, space, mode="read"):
        assert space == "DeviceMemory"
        self.kernel_calls += 1
        return 7.5e11 if mode == "read" else 7.0e11


def test_chip_path_assembly_and_store_hit(tmp_path):
    store = TopologyStore(str(tmp_path))
    runner = _StandInChip()
    topo, _ = discover_pallas(runner=runner, n_samples=9, store=store)
    assert topo.backend == f"pallas-tpu:{KIND}"
    assert topo.general["clock_domain"].value == "ns"
    dm = topo.find_memory("DeviceMemory")
    assert dm.attrs["load_latency"].unit == "ns"
    assert dm.get("load_latency") == pytest.approx(410.0)
    assert dm.get("read_bw") == 750.0 and dm.get("write_bw") == 700.0
    for name, size in (("VMEM", 128 << 20), ("SMEM", 1 << 20)):
        me = topo.find_memory(name)
        assert me.get("size") == size
        assert me.attrs["size"].provenance == "api"
    # only measured or API-reported attributes: nothing modeled
    assert {m.name for m in topo.memory} == {"DeviceMemory", "VMEM", "SMEM"}
    assert all(a.provenance in ("benchmark", "api")
               for m in topo.memory for a in m.attrs.values())
    assert [c.name for c in topo.compute] == ["tensor_cores"]

    calls = runner.kernel_calls
    again, _ = discover_pallas(runner=runner, n_samples=9, store=store)
    assert runner.kernel_calls == calls
    assert again.to_json() == topo.to_json()
    key = request_key(tpu_request_descriptor(KIND, 9))
    res = TopologyService(store).query(key, "hbm.bandwidth")
    assert res.found and res.value == 750.0


@pytest.mark.parametrize("array_bytes,stride", [(16 << 20, 4096),
                                                (64 << 10, 64),
                                                (4096, 4)])
def test_strided_cycle_covers_footprint_at_stride(array_bytes, stride):
    buf, slots = strided_cycle(array_bytes, stride,
                               np.random.default_rng(0))
    assert buf.shape[1] % 128 == 0
    assert slots == array_bytes // stride
    seen, cursor = set(), 0
    for _ in range(slots):
        cursor = int(buf[0, cursor])
        seen.add(cursor)
    assert cursor == 0                        # one cycle, back home
    assert seen == set(range(0, array_bytes // 4, stride // 4))
    assert pchase_reference(buf[0], slots)[0] == 0


# ------------------------------------------------- one process per chip
def test_host_runner_places_arrays_on_the_cpu(monkeypatch):
    targets = []
    real_put = jax.device_put

    def spy(x, device=None, *a, **k):
        targets.append(device)
        return real_put(x, device, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    runner = HostRunner(max_bytes=1 << 20, iters=256)
    runner.pchase("host-cache", 16 << 10, 64, 2)
    assert targets and all(d.platform == "cpu" for d in targets)
    assert runner._cpu.platform == "cpu"


class _EnvReporter:
    """Pool-worker runner whose rows report the worker's JAX_PLATFORMS."""

    def pchase_many(self, requests, n_samples):
        cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
        return np.full((len(requests), n_samples), float(cpu))


def build_env_reporter():
    return _EnvReporter()


def test_pool_workers_start_cpu_only(monkeypatch):
    import pickle

    from repro.core.engine.parallel import (ParallelConfig, ParallelPool,
                                            RunnerSpec)

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # what the coordinator has
    with ParallelPool(ParallelConfig(workers=1)) as pool:
        blob = pickle.dumps(RunnerSpec(build_env_reporter))
        out = pool.run_batch(blob, "pchase_many", [("x", 1, 1)], 3,
                             lambda rows: (rows, 3))
    assert os.environ["JAX_PLATFORMS"] == "tpu"  # coordinator untouched
    assert np.array_equal(out, np.ones((1, 3)))


# ---------------------------------------------------------- compile cache
def _cache_updates(monkeypatch):
    from repro.launch.compile_cache import enable_compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    return enable_compile_cache, updates


def test_compile_cache_dir_from_environment_is_left_to_jax(monkeypatch,
                                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    enable, updates = _cache_updates(monkeypatch)
    assert enable() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable, updates = _cache_updates(monkeypatch)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert enable() == want == str(CHECKOUT_CACHE_DIR)
    assert updates["jax_compilation_cache_dir"] == want
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
