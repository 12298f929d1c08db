"""Probe-engine tests: scheduler contract, batch equivalence, golden topologies.

The engine's correctness claim is strong: batching, caching, and
scheduling must be *invisible* in the results — the engine-based
``discover_sim`` returns the same topology as the legacy sequential loop for
a fixed device seed, and matches ground truth within the same tolerances.
"""
import numpy as np
import pytest

from repro.core import discover_sim, discover_sim_legacy, make_h100_like, \
    make_mi210_like, topology_equivalent
from repro.core.engine import (CachingRunner, SampleCache, WorkItem,
                               run_probes, run_work_items)
from repro.core.probes import SimRunner
from repro.core.stats import ks_change_point, ks_statistic
from repro.core.stats.batch import ks_change_point_scan, ks_statistic_rows

KIB, MIB = 1024, 1024**2


# --------------------------------------------------------------- scheduler
# Dependency shapes, key -> deps, listed with dependents before their
# inputs so that list order alone would violate them.
SHAPES = {
    "chain": {"d": ("c",), "c": ("b",), "b": ("a",), "a": ()},
    "diamond": {"d": ("b", "c"), "c": ("a",), "b": ("a",), "a": ()},
    "fan_in": {"d": ("a", "b", "c"), "a": (), "b": (), "c": ()},
}


class TestScheduler:
    def _items(self, log):
        def mk(name):
            def fn(_results):
                log.append(name)
                return name
            return fn
        return [
            WorkItem(key="a", fn=mk("a"), family="fam"),
            WorkItem(key="b", fn=mk("b"), deps=("a",), family="fam"),
            WorkItem(key="c", fn=mk("c"), deps=("b",), family="fam"),
            WorkItem(key="x", fn=mk("x")),
            WorkItem(key="y", fn=mk("y"), deps=("a", "x")),
        ]

    @pytest.mark.parametrize("shape", sorted(SHAPES))
    def test_dependency_order_respected(self, shape):
        deps = SHAPES[shape]
        seen = []

        def mk(key):
            def fn(results):
                assert all(d in results for d in deps[key])
                seen.append(key)
                return key
            return fn

        sched = run_work_items([WorkItem(key=k, fn=mk(k), deps=d)
                                for k, d in deps.items()])
        assert sched.order == seen
        assert set(sched.order) == set(deps)
        for key, ds in deps.items():
            assert all(sched.order.index(d) < sched.order.index(key)
                       for d in ds)
        assert sched.results == {k: k for k in deps}

    def test_unknown_dep_raises(self):
        with pytest.raises(ValueError, match="unknown deps"):
            run_work_items([WorkItem(key="a", fn=lambda r: 1,
                                     deps=("ghost",))])

    def test_cycle_raises(self):
        items = [WorkItem(key="a", fn=lambda r: 1, deps=("b",)),
                 WorkItem(key="b", fn=lambda r: 1, deps=("a",))]
        with pytest.raises(ValueError, match="cycle"):
            run_work_items(items)

    def test_timings_accumulate_per_family(self):
        from repro.core.discover import DiscoveryTimings
        timings = DiscoveryTimings()
        log = []
        run_work_items(self._items(log), timings=timings)
        assert timings.per_family.get("fam", 0) > 0
        assert timings.total >= timings.per_family["fam"]


# ----------------------------------------------------------- sample cache
class TestSampleCache:
    def test_batch_serves_cached_rows(self):
        runner = CachingRunner(SimRunner(make_h100_like(seed=3)))
        sizes = [32 * KIB, 64 * KIB, 128 * KIB]
        one = runner.pchase("L1", sizes[1], 32, 9)
        rows = runner.pchase_batch("L1", sizes, 32, 9)
        assert runner.cache.hits >= 1          # middle row came from cache
        assert np.array_equal(rows[1], one)
        again = runner.pchase_batch("L1", sizes, 32, 9)
        assert np.array_equal(rows, again)
        assert runner.cache.stats()["entries"] == 3

    def test_cache_hit_equals_rerun(self):
        """Keyed sampling: a cache hit is indistinguishable from re-probing."""
        base = SimRunner(make_h100_like(seed=3))
        cached = CachingRunner(base, cache=SampleCache())
        a = cached.pchase("L1", 96 * KIB, 32, 17)
        b = base.pchase("L1", 96 * KIB, 32, 17)      # fresh, uncached
        assert np.array_equal(a, b)


# ------------------------------------------------------- batched equivalence
class TestBatchedRunner:
    def test_pchase_batch_rows_match_individual_calls(self):
        runner = SimRunner(make_h100_like(seed=9))
        sizes = list(range(64 * KIB, 64 * KIB + 32 * 40, 32))
        batch = runner.pchase_batch("L1", sizes, 32, 17)
        for i, ab in enumerate(sizes):
            assert np.array_equal(batch[i], runner.pchase("L1", ab, 32, 17))

    def test_cold_chase_batch_rows_match_individual_calls(self):
        """The §IV-D sweep batch: per-row strides AND array sizes (unlike
        ``pchase_batch``, which varies only the size)."""
        runner = SimRunner(make_h100_like(seed=9))
        strides = [4, 8, 32, 64, 128]
        arrs = [max(64 * KIB, s * 65) for s in strides]
        batch = runner.cold_chase_batch("L1", arrs, strides, 64)
        for i, (ab, s) in enumerate(zip(arrs, strides)):
            assert np.array_equal(batch[i],
                                  runner.cold_chase("L1", ab, s, 64))

    def test_cold_chase_batch_served_through_cache(self):
        runner = CachingRunner(SimRunner(make_h100_like(seed=9)))
        strides = [4, 8, 32]
        arrs = [max(64 * KIB, s * 65) for s in strides]
        one = runner.cold_chase("L1", arrs[1], strides[1], 64)
        rows = runner.cold_chase_batch("L1", arrs, strides, 64)
        assert runner.cache.hits >= 1              # middle row from cache
        assert np.array_equal(rows[1], one)
        again = runner.cold_chase_batch("L1", arrs, strides, 64)
        assert np.array_equal(rows, again)

    def test_fetch_granularity_batched_equals_sequential(self):
        from repro.core.probes import find_fetch_granularity

        for make, space in ((make_h100_like, "L1"), (make_mi210_like, "vL1")):
            seq = find_fetch_granularity(SimRunner(make(seed=7)), space,
                                         n_samples=17)
            bat = find_fetch_granularity(
                CachingRunner(SimRunner(make(seed=7))), space,
                n_samples=17, batched=True)
            assert (seq.granularity, seq.found) == (bat.granularity, bat.found)
            assert np.array_equal(seq.strides, bat.strides)
            assert np.array_equal(seq.mixed, bat.mixed)

    def test_vectorized_ks_scan_matches_sequential_scan(self):
        rng = np.random.default_rng(1)
        for trial in range(20):
            n = int(rng.integers(10, 100))
            s = rng.normal(20, 1, n)
            if trial % 2:
                s[n // 2:] += rng.uniform(5, 50)
            for mode in ("best", "first"):
                a = ks_change_point(s, alpha=0.01, mode=mode)
                b = ks_change_point_scan(s, alpha=0.01, mode=mode)
                assert (a.index, a.found, a.statistic, a.pvalue,
                        a.confidence, a.candidates) == \
                       (b.index, b.found, b.statistic, b.pvalue,
                        b.confidence, b.candidates)

    def test_ks_statistic_rows_matches_per_row(self):
        rng = np.random.default_rng(2)
        rows = np.round(rng.normal(0, 1, (12, 33)), 1)   # ties included
        ref = np.round(rng.normal(0.5, 1, 25), 1)
        got = ks_statistic_rows(rows, ref)
        want = np.array([ks_statistic(r, ref) for r in rows])
        assert np.array_equal(got, want)


# --------------------------------------------------- engine == legacy, golden
class TestEngineEqualsLegacy:
    @pytest.mark.parametrize("make,seed", [
        (make_h100_like, 11), (make_h100_like, 48),
        (make_mi210_like, 12), (make_mi210_like, 48),
    ])
    def test_equivalent_topology_for_fixed_seed(self, make, seed):
        """Engine == legacy, per the ROADMAP-prescribed contract: discrete
        attributes (sizes, line sizes, granularities, amounts, sharing)
        exactly equal, float metrics within relative tolerance — vectorized
        statistics (the ``_l1_refine`` window) cannot promise bit-equal
        float summation order, only equal decisions."""
        topo_l, tl = discover_sim_legacy(make(seed=seed), n_samples=17)
        topo_e, te = discover_sim(make(seed=seed), n_samples=17)
        assert topology_equivalent(topo_l, topo_e, rel_tol=1e-6)
        # per-family accounting preserved: same buckets measured
        assert set(te.per_family) >= {"size", "latency", "bandwidth"}

    def test_equivalence_is_discrete_strict(self):
        """The relaxed contract still rejects discrete drift: a one-byte
        size change or a provenance flip must not count as equivalent."""
        topo_a, _ = discover_sim(make_h100_like(seed=5), n_samples=9)
        topo_b, _ = discover_sim(make_h100_like(seed=5), n_samples=9)
        assert topology_equivalent(topo_a, topo_b)
        l1 = topo_b.find_memory("L1")
        l1.attrs["size"].value += 1
        assert not topology_equivalent(topo_a, topo_b)
        l1.attrs["size"].value -= 1
        assert topology_equivalent(topo_a, topo_b)
        # floats move within tolerance ... and only within it
        l1.attrs["load_latency"].value *= 1.0 + 1e-9
        assert topology_equivalent(topo_a, topo_b)
        l1.attrs["load_latency"].value *= 1.01
        assert not topology_equivalent(topo_a, topo_b)

    def test_cache_hits_counted_during_discovery(self):
        eng = run_probes(SimRunner(make_h100_like(seed=6)), n_samples=9,
                         device_families=("sharing", "device_memory_latency",
                                          "device_memory_bandwidth"))
        assert eng.cache_stats["hits"] > 0
        assert eng.cache_stats["misses"] > 0
        # every scheduled item completed
        assert len(eng.order) == sum(len(v) for v in
                                     eng.space_results.values()) + 3


class TestGoldenTopology:
    """Engine-based discovery vs ground truth, same tolerances as the legacy
    path's test_discovery assertions (in-repo Table III)."""

    @pytest.fixture(scope="class")
    def h100(self):
        topo, _ = discover_sim(make_h100_like(seed=11), n_samples=17)
        return topo

    @pytest.fixture(scope="class")
    def mi210(self):
        topo, _ = discover_sim(make_mi210_like(seed=12), n_samples=17)
        return topo

    def test_h100_l1(self, h100):
        l1 = h100.find_memory("L1")
        assert abs(l1.get("size") - 238 * KIB) <= 2 * KIB
        assert abs(l1.get("load_latency") - 38.0) < 4.0
        assert l1.get("line_size") == 128
        assert l1.get("fetch_granularity") == 32
        assert l1.get("amount") == 1

    def test_h100_l2_and_device_memory(self, h100):
        l2 = h100.find_memory("L2")
        assert l2.get("amount") == 2
        assert abs(l2.get("segment_size") - 25 * MIB) <= MIB
        dm = h100.find_memory("DeviceMemory")
        assert abs(dm.get("load_latency") - 843) < 60

    def test_h100_unified_l1_sharing(self, h100):
        l1 = h100.find_memory("L1")
        assert set(l1.shared_with) >= {"Texture", "Readonly"}
        assert "L1" not in h100.find_memory("ConstL1").shared_with

    def test_mi210_levels_and_cu_sharing(self, mi210):
        vl1 = mi210.find_memory("vL1")
        assert abs(vl1.get("size") - 16 * KIB) <= KIB
        assert vl1.get("fetch_granularity") == 64
        sl1d = mi210.find_memory("sL1d")
        assert sl1d.get("exclusive_cus")
        assert any("," in g for g in sl1d.shared_with)
