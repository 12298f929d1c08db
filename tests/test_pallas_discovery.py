"""End-to-end Pallas-backend discovery (ISSUE 3 acceptance).

``discover_pallas()`` must produce a ``Topology`` through the *shared*
engine path whose discrete attributes match the backend's configured
ground truth, persist it content-addressed in the ``TopologyStore``, and
serve it through ``TopologyService`` — proving the registry/scheduler/
store stack is genuinely backend-neutral.

Everything here executes real Pallas kernels in interpret mode, so the
module is ``slow``-marked; the fast lane keeps its budget.
"""
import json

import numpy as np
import pytest

from repro.core import discover_pallas
from repro.core.discover import pallas_request_descriptor
from repro.core.engine.store import TopologyStore, request_key
from repro.core.probes import PallasRunner, make_pallas_model
from repro.serve.topology_service import TopologyService

pytestmark = pytest.mark.slow

N_SAMPLES = 9


@pytest.fixture(scope="module")
def discovery(tmp_path_factory):
    """One store-backed discovery shared by the whole module.

    One retry on a discrete mismatch: the rows are real timed measurements
    and a sustained steal burst on a shared CI box can defeat even the
    drift-hardened detection (a few-percent tail); a genuine code
    regression fails both independent attempts."""
    for attempt in range(2):
        store = TopologyStore(str(tmp_path_factory.mktemp("pallas-store")))
        model = make_pallas_model()
        runner = PallasRunner(model, interpret=True)
        topo, timings = discover_pallas(runner=runner, interpret=True,
                                        n_samples=N_SAMPLES,
                                        store=store)
        gt = model.ground_truth()
        l1 = topo.find_memory("L1")
        clean = l1 is not None \
            and l1.get("size") == gt["L1"]["size"] \
            and l1.get("line_size") == gt["L1"]["line_size"] \
            and l1.get("fetch_granularity") == gt["L1"]["fetch_granularity"] \
            and l1.get("amount") == 1
        if attempt == 0 and not clean:
            continue
        return {"store": store, "model": model, "runner": runner,
                "topo": topo, "timings": timings}


class TestDiscreteGroundTruth:
    """Sizes / line size / fetch granularity / amount vs the configured
    hierarchy: exact for cache spaces (their sweep grids align to the
    power-of-two capacities), one sweep-grid step (<= 64 B) of quantization
    allowed on the word-granular scratchpad."""

    def test_cache_spaces_exact(self, discovery):
        gt = discovery["model"].ground_truth()
        for name in ("L1", "L2"):
            me = discovery["topo"].find_memory(name)
            assert me is not None
            assert me.get("size") == gt[name]["size"]
            assert me.get("line_size") == gt[name]["line_size"]
            assert me.get("fetch_granularity") == gt[name]["fetch_granularity"]

    def test_l1_amount(self, discovery):
        me = discovery["topo"].find_memory("L1")
        assert me.get("amount") == 1

    def test_scratchpad_size_within_grid_step(self, discovery):
        gt = discovery["model"].ground_truth()
        vmem = discovery["topo"].find_memory("VMEM")
        assert vmem is not None
        assert abs(vmem.get("size") - gt["VMEM"]["size"]) <= 64
        # ... and no cold-pass attributes: the capability flag held.
        assert vmem.get("fetch_granularity") is None
        assert vmem.get("line_size") is None

    def test_latencies_in_model_cycle_units(self, discovery):
        """Calibration-normalized samples land near the modeled cycle
        counts (generous bounds: values are real timing ratios)."""
        gt = discovery["model"].ground_truth()
        for name in ("L1", "VMEM", "L2"):
            me = discovery["topo"].find_memory(name)
            want = gt[name]["latency"]
            assert abs(me.get("load_latency") - want) / want < 0.5

    def test_provenance_and_backend_identity(self, discovery):
        topo = discovery["topo"]
        assert topo.backend.startswith("pallas-interp:")
        l1 = discovery["topo"].find_memory("L1")
        assert l1.attrs["size"].provenance == "benchmark"
        assert l1.attrs["size"].confidence is not None

    def test_shared_engine_path_families(self, discovery):
        """The per-family timing buckets prove the run went through the
        same registry/scheduler as the sim backend."""
        fams = set(discovery["timings"].per_family)
        assert fams >= {"size", "latency", "bandwidth",
                        "fetch_granularity", "line_size"}

    def test_kernels_actually_ran(self, discovery):
        assert discovery["runner"].kernel_calls > 100


class TestStoreIntegration:
    def test_content_addressed_persist(self, discovery):
        key = request_key(pallas_request_descriptor(
            discovery["model"], N_SAMPLES, None))
        assert discovery["store"].has(key)
        entry = discovery["store"].get(key)
        assert entry.meta["request"]["kind"] == "discover_pallas"

    def test_store_hit_returns_without_kernels(self, discovery):
        calls_before = discovery["runner"].kernel_calls
        topo2, timings2 = discover_pallas(
            runner=discovery["runner"], interpret=True, n_samples=N_SAMPLES,
            store=discovery["store"])
        assert discovery["runner"].kernel_calls == calls_before
        assert topo2.to_json() == discovery["topo"].to_json()
        # stored per-family timings reconstructed on the hit
        assert timings2.per_family == dict(discovery["timings"].per_family)

    def test_distinct_requests_distinct_keys(self, discovery):
        model = discovery["model"]
        k_a = request_key(pallas_request_descriptor(model, N_SAMPLES, None))
        k_b = request_key(pallas_request_descriptor(model, N_SAMPLES + 2,
                                                    None))
        k_c = request_key(pallas_request_descriptor(model, N_SAMPLES,
                                                    ["L1"]))
        assert len({k_a, k_b, k_c}) == 3


class TestServiceIntegration:
    def test_queryable_through_topology_service(self, discovery):
        svc = TopologyService(discovery["store"])
        key = request_key(pallas_request_descriptor(
            discovery["model"], N_SAMPLES, None))
        gt = discovery["model"].ground_truth()
        res = svc.query(key, "L1.size")
        assert res.found and res.value == gt["L1"]["size"]
        res = svc.query(key, "L2.fetch_granularity")
        assert res.found and res.value == gt["L2"]["fetch_granularity"]
        res = svc.query(key, "hbm.latency")       # DeviceMemory alias
        assert res.found and res.value > 0

    def test_batched_queries_and_attributes_filter(self, discovery):
        svc = TopologyService(discovery["store"])
        key = request_key(pallas_request_descriptor(
            discovery["model"], N_SAMPLES, None))
        answers = svc.query_batch([(key, "L1.size"), (key, "VMEM.latency"),
                                   (key, "L2.read_bw")])
        assert all(a.found for a in answers)
        benchmarked = svc.attributes(key, provenance="benchmark")
        assert {a.path for a in benchmarked} >= {"L1.size", "L1.line_size"}
