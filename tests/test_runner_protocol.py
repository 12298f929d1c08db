"""ProbeRunner conformance suite — one contract, three backends.

The probe workflows are runner-agnostic; this suite pins down what that
means operationally by running the same assertions against ``SimRunner``,
``HostRunner``, and ``PallasRunner``: protocol shape, sample array
shapes/dtypes, batch==loop equivalence (exact for runners with
request-keyed deterministic streams, structural for runners whose samples
are real wall-time measurements), and ``SpaceInfo`` capability flags being
honored by both the runners and the engine registry.

Pallas parameters are marked ``slow`` (interpret-mode kernels compile on
first touch); the fast lane runs the sim/host rows.
"""
import os

import numpy as np
import pytest

from repro.core import make_h100_like
from repro.core.discover import (DiscoveryRequest, discover,
                                 sim_request_descriptor)
from repro.core.engine.cache import CachingRunner
from repro.core.engine.parallel import (ParallelConfig, ParallelPool,
                                        effective_cpu_count,
                                        get_global_pool,
                                        maybe_parallel_runner,
                                        shutdown_global_pools)
from repro.core.engine.registry import space_probe_specs
from repro.core.errors import Resilience, TransientRunnerError
from repro.core.probes import (ChaosRunner, FaultSchedule, HostRunner,
                               PallasRunner, ProbeRunner, SimRunner,
                               make_pallas_model, random_cycle,
                               sattolo_cycle)
from repro.core.topology import topology_equivalent

KIB, MIB = 1024, 1024**2

# Per backend: runner factory, a bandwidth-capable space, and whether
# cold-pass requests on unsupported spaces must raise (the measuring
# backends have no cold-pass control at all / outside cache spaces; the
# simulator can serve them even where discovery never asks).  The "chaos"
# row is a ``ChaosRunner`` under a zero-fault schedule: the fault-injection
# proxy must itself be a conforming ``ProbeRunner`` (same shapes, same
# batch==loop contract) or every fault-tolerance result built on it would
# be suspect.
BACKENDS = {
    "sim": dict(
        make=lambda: SimRunner(make_h100_like(seed=3)),
        bw_space="L2",
        cold_unsupported_raises=False,
    ),
    "chaos": dict(
        make=lambda: ChaosRunner(SimRunner(make_h100_like(seed=3)),
                                 FaultSchedule(seed=1)),
        bw_space="L2",
        cold_unsupported_raises=False,
    ),
    "host": dict(
        make=lambda: HostRunner(max_bytes=8 * MIB, iters=1 << 12),
        bw_space="DRAM",
        cold_unsupported_raises=True,
    ),
    "pallas": dict(
        make=lambda: PallasRunner(make_pallas_model(), interpret=True,
                                  base_steps=2048,
                                  cold_reps=2),
        bw_space="L2",
        cold_unsupported_raises=True,
    ),
}

PARAMS = [
    pytest.param("sim", id="sim"),
    pytest.param("chaos", id="chaos"),
    pytest.param("host", id="host"),
    pytest.param("pallas", id="pallas", marks=pytest.mark.slow),
]


@pytest.fixture(scope="module", params=PARAMS)
def backend(request):
    cfg = BACKENDS[request.param]
    return {"name": request.param, "runner": cfg["make"](), **cfg}


def _probe_space(runner):
    """A (space, in-capacity array size) pair valid for any backend."""
    info = runner.spaces()[0]
    return info, min(info.max_bytes // 8, 64 * KIB)


class TestProtocolSurface:
    def test_satisfies_probe_runner_protocol(self, backend):
        assert isinstance(backend["runner"], ProbeRunner)

    def test_declares_determinism(self, backend):
        # chaos over sim under a value-preserving schedule is still
        # deterministic: replayed faults, unperturbed samples.
        det = backend["runner"].deterministic
        assert isinstance(det, bool)
        assert det == (backend["name"] in ("sim", "chaos"))

    def test_spaces_well_formed(self, backend):
        infos = backend["runner"].spaces()
        assert infos
        names = [i.name for i in infos]
        assert len(set(names)) == len(names)
        for i in infos:
            assert i.kind in ("cache", "scratchpad", "memory")
            assert i.max_bytes > 0


class TestPChase:
    def test_sample_shape_and_domain(self, backend):
        info, ab = _probe_space(backend["runner"])
        out = np.asarray(backend["runner"].pchase(info.name, ab, 32, 7))
        assert out.shape == (7,)
        assert out.dtype.kind == "f"
        assert np.all(np.isfinite(out)) and np.all(out > 0)

    def test_batch_equals_loop(self, backend):
        runner = backend["runner"]
        info, ab = _probe_space(runner)
        sizes = [ab, ab * 2, ab * 3]
        batch = np.asarray(runner.pchase_batch(info.name, sizes, 32, 7))
        assert batch.shape == (3, 7)
        assert np.all(np.isfinite(batch)) and np.all(batch > 0)
        if runner.deterministic:
            for i, size in enumerate(sizes):
                assert np.array_equal(
                    batch[i], runner.pchase(info.name, size, 32, 7))


class TestColdChase:
    def test_supported_spaces_serve_per_load_rows(self, backend):
        runner = backend["runner"]
        cold = [i for i in runner.spaces() if i.supports_cold]
        if not cold:
            pytest.skip("backend advertises no cold-pass space")
        info = cold[0]
        out = np.asarray(runner.cold_chase(info.name, 64 * KIB, 32, 65))
        assert out.ndim == 1 and out.size > 0
        assert np.all(np.isfinite(out)) and np.all(out > 0)

    def test_batch_equals_loop(self, backend):
        runner = backend["runner"]
        cold = [i for i in runner.spaces() if i.supports_cold]
        if not cold:
            pytest.skip("backend advertises no cold-pass space")
        info = cold[0]
        strides = [8, 32, 64]
        arrs = [max(64 * KIB, s * 65) for s in strides]
        batch = np.asarray(runner.cold_chase_batch(info.name, arrs, strides,
                                                   64))
        assert batch.shape[0] == 3
        assert np.all(np.isfinite(batch)) and np.all(batch > 0)
        if runner.deterministic:
            for i, (ab, s) in enumerate(zip(arrs, strides)):
                assert np.array_equal(
                    batch[i], runner.cold_chase(info.name, ab, s, 64))

    def test_capability_flag_respected(self, backend):
        """Spaces without cold-pass support must be refused by measuring
        runners — the engine relies on the flag, and a silent wrong answer
        would be worse than the exception."""
        runner = backend["runner"]
        uncold = [i for i in runner.spaces() if not i.supports_cold]
        if not (uncold and backend["cold_unsupported_raises"]):
            pytest.skip("no refusing space on this backend")
        with pytest.raises(NotImplementedError):
            runner.cold_chase(uncold[0].name, 64 * KIB, 32, 65)


class TestEvictionProbes:
    def test_amount_probe_or_refusal(self, backend):
        runner = backend["runner"]
        amount = [i for i in runner.spaces() if i.supports_amount]
        if amount:
            info = amount[0]
            ab = int(info.max_bytes // 8 * 0.9)
            out = np.asarray(runner.amount_probe(info.name, 0, 1, ab, 7))
            assert out.shape == (7,) and np.all(out > 0)
        else:
            with pytest.raises(NotImplementedError):
                runner.amount_probe("anything", 0, 1, 4 * KIB, 7)

    def test_sharing_probe_or_refusal(self, backend):
        runner = backend["runner"]
        sharing = [i for i in runner.spaces() if i.supports_sharing]
        if sharing:
            info = sharing[0]
            ab = int(info.max_bytes // 8 * 0.9)
            out = np.asarray(
                runner.sharing_probe(info.name, info.name, ab, 7))
            assert out.shape == (7,) and np.all(out > 0)
        else:
            with pytest.raises(NotImplementedError):
                runner.sharing_probe("a", "b", 4 * KIB, 7)


class TestEvictionMany:
    """The heterogeneous eviction-grid capability (§IV-F/G/H fused rows)."""

    @staticmethod
    def _mixed_requests(runner):
        """Mixed amount/sharing/cu rows from whatever the backend supports."""
        reqs = []
        amount = [i for i in runner.spaces() if i.supports_amount]
        if amount:
            info = amount[0]
            ab = int(info.max_bytes // 8 * 0.9)
            reqs += [("amount", info.name, 0, 1, ab),
                     ("amount", info.name, 0, 2, ab)]
        sharing = [i for i in runner.spaces() if i.supports_sharing]
        if sharing:
            info = sharing[0]
            ab = int(info.max_bytes // 8 * 0.9)
            reqs.append(("sharing", info.name, info.name, ab))
        cu_ids = runner.cu_ids() if hasattr(runner, "cu_ids") else []
        if len(cu_ids) >= 2:
            sl1d = next(i for i in runner.spaces() if i.name == "sL1d")
            reqs.append(("cu", "sL1d", cu_ids[0], cu_ids[1],
                         int(sl1d.max_bytes // 8 * 0.9)))
        return reqs

    def test_batch_equals_loop(self, backend):
        """One grid dispatch must reproduce the per-kind single probes —
        bit-identical on deterministic runners, structurally valid on
        measuring ones.  Single-actor backends must refuse instead."""
        runner = backend["runner"]
        reqs = self._mixed_requests(runner)
        if not reqs:
            with pytest.raises(NotImplementedError):
                runner.eviction_many(
                    [("amount", "anything", 0, 1, 4 * KIB)], 7)
            return
        batch = np.asarray(runner.eviction_many(reqs, 7))
        assert batch.shape == (len(reqs), 7)
        assert np.all(np.isfinite(batch)) and np.all(batch > 0)
        if not runner.deterministic:
            return
        for i, req in enumerate(reqs):
            if req[0] == "amount":
                row = runner.amount_probe(req[1], req[2], req[3], req[4], 7)
            elif req[0] == "sharing":
                row = runner.sharing_probe(req[1], req[2], req[3], 7)
            else:
                row = runner.cu_sharing_probe(req[2], req[3], req[4], 7,
                                              space=req[1])
            assert np.array_equal(batch[i], np.asarray(row)), req

    def test_cu_rows_bit_identical_on_cu_device(self):
        """AMD-style device: fused cu rows == cu_sharing_probe, exactly."""
        from repro.core import make_mi210_like

        runner = SimRunner(make_mi210_like(seed=5))
        ids = runner.cu_ids()
        assert len(ids) >= 2
        sl1d = next(i for i in runner.spaces() if i.name == "sL1d")
        ab = int(sl1d.max_bytes // 8 * 0.9)
        reqs = [("cu", "sL1d", ids[0], b, ab) for b in ids[1:4]]
        batch = np.asarray(runner.eviction_many(reqs, 9))
        for i, (_, _, a, b, arr) in enumerate(reqs):
            assert np.array_equal(
                batch[i],
                np.asarray(runner.cu_sharing_probe(a, b, arr, 9)))

    def test_unknown_kind_rejected(self):
        runner = SimRunner(make_h100_like(seed=3))
        with pytest.raises(ValueError):
            runner.eviction_many([("park", "L1", 0, 1, 4 * KIB)], 7)

    def test_caching_runner_dedupes_and_replays(self):
        """Duplicate rows in one grid cost one base fetch; a repeat call —
        or a later single-probe of the same request — costs zero."""
        from repro.core.engine import SampleCache
        from repro.core.engine.cache import CachingRunner

        runner = CachingRunner(SimRunner(make_h100_like(seed=3)),
                               cache=SampleCache())
        reqs = self._mixed_requests(runner)
        assert reqs
        doubled = reqs + [reqs[0]]
        first = np.asarray(runner.eviction_many(doubled, 7))
        assert runner.cache.stats()["misses"] == len(reqs)
        assert np.array_equal(first[0], first[-1])

        again = np.asarray(runner.eviction_many(doubled, 7))
        assert runner.cache.stats()["misses"] == len(reqs)  # all hits now
        assert np.array_equal(first, again)
        # single-probe replay of a grid-fetched row: also a hit
        a = reqs[0]
        runner.amount_probe(a[1], a[2], a[3], a[4], 7)
        assert runner.cache.stats()["misses"] == len(reqs)


class TestChaosRunner:
    """Chaos-specific halves of the contract: transparent when idle,
    deterministic when faulting (the property every fault-tolerance test
    and the ``fault_recovery`` bench gate lean on)."""

    def _base(self):
        return SimRunner(make_h100_like(seed=3))

    def test_zero_fault_schedule_is_bit_transparent(self):
        """No schedule -> every sample identical to the wrapped runner."""
        chaos, base = ChaosRunner(self._base()), self._base()
        info = base.spaces()[0]
        ab = min(info.max_bytes // 8, 64 * KIB)
        assert np.array_equal(chaos.pchase(info.name, ab, 32, 9),
                              base.pchase(info.name, ab, 32, 9))
        assert np.array_equal(
            np.asarray(chaos.pchase_batch(info.name, [ab, 2 * ab], 32, 9)),
            np.asarray(base.pchase_batch(info.name, [ab, 2 * ab], 32, 9)))
        assert chaos.faults_injected == 0

    def test_fault_replay_is_deterministic(self):
        """Two fresh runners over the same schedule fault on exactly the
        same calls — chaos runs are reproducible by construction."""
        sched = FaultSchedule(seed=42, transient_rate=0.3,
                              max_faults_per_request=2)

        def trace():
            chaos = ChaosRunner(self._base(), sched)
            info = chaos.spaces()[0]
            ab = min(info.max_bytes // 8, 64 * KIB)
            events = []
            for size in (ab, 2 * ab, 3 * ab):
                for _ in range(4):             # retries consume the budget
                    try:
                        chaos.pchase(info.name, size, 32, 9)
                        events.append(("ok", size))
                    except TransientRunnerError:
                        events.append(("fault", size))
            return events, chaos.faults_injected

        assert trace() == trace()

    def test_fault_budget_lets_retries_succeed(self):
        """Per-request fault budget: after ``max_faults_per_request``
        raises, the same request must succeed — retry loops terminate."""
        sched = FaultSchedule(seed=0, transient_rate=1.0,
                              max_faults_per_request=2)
        chaos = ChaosRunner(self._base(), sched)
        info = chaos.spaces()[0]
        ab = min(info.max_bytes // 8, 64 * KIB)
        for _ in range(2):
            with pytest.raises(TransientRunnerError):
                chaos.pchase(info.name, ab, 32, 9)
        out = np.asarray(chaos.pchase(info.name, ab, 32, 9))
        assert out.shape == (9,)
        assert chaos.faults_injected == 2

    def test_jitter_preserves_batch_equals_loop(self):
        """Perturbations are keyed by the per-row request signature, so a
        fused row and its single-call twin see the same noise — the
        batch==loop equivalence the engine's caching depends on."""
        sched = FaultSchedule(seed=9, jitter=0.05, outlier_rate=0.05)
        chaos = ChaosRunner(self._base(), sched)
        info = chaos.spaces()[0]
        ab = min(info.max_bytes // 8, 64 * KIB)
        sizes = [ab, 2 * ab, 3 * ab]
        batch = np.asarray(chaos.pchase_batch(info.name, sizes, 32, 9))
        for i, size in enumerate(sizes):
            assert np.array_equal(batch[i],
                                  np.asarray(chaos.pchase(info.name, size,
                                                          32, 9)))
        # ...and the jitter is actually doing something vs the base
        base = self._base()
        assert not np.array_equal(batch[0],
                                  np.asarray(base.pchase(info.name, ab, 32,
                                                         9)))

    def test_permanent_kind_always_faults(self):
        sched = FaultSchedule(seed=3, permanent_kinds=("bandwidth",))
        chaos = ChaosRunner(self._base(), sched)
        for _ in range(4):
            with pytest.raises(TransientRunnerError):
                chaos.bandwidth("L2", "read")
        # other kinds stay clean
        info = chaos.spaces()[0]
        ab = min(info.max_bytes // 8, 64 * KIB)
        assert np.asarray(chaos.pchase(info.name, ab, 32, 9)).shape == (9,)

    def test_kill_after_terminates_run(self):
        sched = FaultSchedule(seed=3, kill_after=2)
        chaos = ChaosRunner(self._base(), sched)
        info = chaos.spaces()[0]
        ab = min(info.max_bytes // 8, 64 * KIB)
        chaos.pchase(info.name, ab, 32, 9)
        chaos.pchase(info.name, 2 * ab, 32, 9)
        with pytest.raises(RuntimeError, match="chaos kill"):
            chaos.pchase(info.name, 3 * ab, 32, 9)


class TestBandwidth:
    def test_read_write_positive(self, backend):
        runner = backend["runner"]
        for mode in ("read", "write"):
            bw = runner.bandwidth(backend["bw_space"], mode)
            assert isinstance(bw, float) and bw > 0


class TestRegistryHonorsFlags:
    """The engine side of the capability contract: families never scheduled
    for spaces that do not support them, for every backend's spaces."""

    def test_cold_families_gated(self, backend):
        for info in backend["runner"].spaces():
            families = {s.family for s in space_probe_specs(info)}
            if not info.supports_cold:
                assert "fetch_granularity" not in families
                assert "line_size" not in families
            else:
                assert "fetch_granularity" in families
            if not (info.supports_amount or info.scope == "chip"):
                assert "amount" not in families


class TestPermutations:
    def test_random_cycle_is_single_cycle(self):
        rng = np.random.default_rng(0)
        for n in (2, 5, 64, 1000):
            perm = random_cycle(n, rng)
            seen, cur = set(), 0
            for _ in range(n):
                cur = int(perm[cur])
                assert cur not in seen
                seen.add(cur)
            assert cur == 0 and len(seen) == n

    def test_matches_sattolo_distribution_property(self):
        # Both constructions produce permutations with exactly one cycle.
        rng = np.random.default_rng(1)
        for n in (8, 33):
            for perm in (sattolo_cycle(n, rng), random_cycle(n, rng)):
                visited = set()
                cur = 0
                while cur not in visited:
                    visited.add(cur)
                    cur = int(perm[cur])
                assert len(visited) == n


# --------------------------------------------------------------------------
# Multiprocess parallel dispatch (engine/parallel.py)
# --------------------------------------------------------------------------
# workers=2 with a one-row shard floor forces every multi-row batch to
# actually split across processes — the strongest form of the sharded ==
# inline claim.  Explicit ``workers`` bypasses the effective-core floor so
# the suite exercises real pooling even on a 1-2 core CI box.
PCFG = ParallelConfig(workers=2, min_rows_per_shard=1)

DEVICE_FAMILIES = ("sharing", "device_memory_latency",
                   "device_memory_bandwidth")


def _shm_residue(prefix):
    """Shared-memory segment names under /dev/shm carrying ``prefix``.

    Empty on platforms that mount no /dev/shm — the residue backstop is
    POSIX-shm specific, and so is the leak it guards against.
    """
    if not os.path.isdir("/dev/shm"):
        return []
    return [n for n in os.listdir("/dev/shm") if n.startswith(prefix)]


@pytest.fixture(scope="module")
def pool():
    """One dedicated pool for the conformance tests (isolated lifecycle)."""
    with ParallelPool(PCFG) as p:
        yield p


# Deterministic request-keyed runners: sharding must be byte-for-byte
# invisible.  The "caching" row wraps the sim runner in ``CachingRunner``,
# whose ``runner_spec`` delegates to its base — workers rebuild the bare
# runner and the cache stays coordinator-side.
DET_RUNNERS = [
    pytest.param(lambda: SimRunner(make_h100_like(seed=3)), id="sim"),
    pytest.param(lambda: ChaosRunner(SimRunner(make_h100_like(seed=3)),
                                     FaultSchedule(seed=1)), id="chaos"),
    pytest.param(lambda: CachingRunner(SimRunner(make_h100_like(seed=3))),
                 id="caching"),
]


def _eviction_reqs(runner):
    """A mixed amount/sharing request grid big enough to shard."""
    reqs = []
    amount = [i for i in runner.spaces() if i.supports_amount][0]
    ab = min(amount.max_bytes // 8, 64 * KIB)
    reqs += [("amount", amount.name, 0, w, ab) for w in range(4)]
    sharing = [i for i in runner.spaces() if i.supports_sharing][0]
    sab = min(sharing.max_bytes // 8, 64 * KIB)
    reqs += [("sharing", sharing.name, sharing.name, sab),
             ("sharing", sharing.name, sharing.name, sab // 2)]
    return reqs


class TestParallelDispatch:
    """Sharded pool execution == inline execution, byte for byte.

    The pool's whole correctness argument rests on request-keyed sampling:
    each probe row derives its stream from (request, sample index) alone,
    so *where* the row runs cannot matter.  These tests pin that down for
    every pooled capability and every spec-publishing runner, then check
    the failure half of the contract: worker death surfaces as
    ``TransientRunnerError`` (the resilience currency), the pool respawns,
    and no shared-memory segment outlives its call.
    """

    @pytest.mark.parametrize("make", DET_RUNNERS)
    def test_five_capabilities_bit_identical(self, pool, make):
        inline = make()
        pooled = maybe_parallel_runner(make(), PCFG, pool=pool)
        assert pooled is not inline and pooled.deterministic

        sizes = [16 * KIB + 4 * KIB * i for i in range(9)]
        assert np.array_equal(inline.pchase_batch("L1", sizes, 32, 7),
                              pooled.pchase_batch("L1", sizes, 32, 7))

        strides = [8 * (i + 1) for i in range(9)]
        assert np.array_equal(
            inline.cold_chase_batch("L1", [64 * KIB] * 9, strides, 7),
            pooled.cold_chase_batch("L1", [64 * KIB] * 9, strides, 7))

        reqs = ([("L1", 16 * KIB + 4 * KIB * i, 32) for i in range(6)]
                + [("L2", MIB + 256 * KIB * i, 64) for i in range(3)])
        assert np.array_equal(inline.pchase_many(reqs, 7),
                              pooled.pchase_many(reqs, 7))
        assert np.array_equal(inline.cold_chase_many(reqs, 7),
                              pooled.cold_chase_many(reqs, 7))

        ev = _eviction_reqs(inline)
        assert np.array_equal(inline.eviction_many(ev, 7),
                              pooled.eviction_many(ev, 7))

    def test_batches_actually_shard_across_workers(self, pool):
        pooled = maybe_parallel_runner(SimRunner(make_h100_like(seed=3)),
                                       PCFG, pool=pool)
        calls0, shards0 = pool.calls, pool.shards
        pooled.pchase_many([("L1", 32 * KIB + 4 * KIB * i, 32)
                            for i in range(16)], 5)
        assert pool.calls == calls0 + 1
        assert pool.shards == shards0 + 2       # both workers took rows
        # A single-row batch cannot split below one row per shard.
        pooled.pchase_many([("L1", 32 * KIB, 32)], 5)
        assert pool.shards == shards0 + 3

    def test_host_structural_through_pool(self, pool):
        """Measuring runners pool too — structurally, never bit-for-bit."""
        pooled = maybe_parallel_runner(
            HostRunner(max_bytes=8 * MIB, iters=1 << 10), PCFG, pool=pool)
        info, ab = _probe_space(pooled)
        rows = np.asarray(pooled.pchase_many(
            [(info.name, ab, 64), (info.name, ab // 2, 64)], 3))
        assert rows.shape == (2, 3) and rows.dtype == np.float64
        assert np.all(np.isfinite(rows)) and np.all(rows > 0)
        # Capability refusals keep their exception type across the pool.
        with pytest.raises(NotImplementedError):
            pooled.cold_chase_many([(info.name, ab, 64)], 3)

    def test_caching_over_pool_serves_repeats_locally(self, pool):
        """Engine ordering: cache above the pool, misses-only cross over."""
        reqs = [("L1", 16 * KIB + 4 * KIB * i, 32) for i in range(8)]
        inline = CachingRunner(SimRunner(make_h100_like(seed=3)))
        cached = CachingRunner(maybe_parallel_runner(
            SimRunner(make_h100_like(seed=3)), PCFG, pool=pool))
        assert np.array_equal(inline.pchase_many(reqs, 7),
                              cached.pchase_many(reqs, 7))
        calls0 = pool.calls
        cached.pchase_many(reqs, 7)             # all rows now cached
        assert pool.calls == calls0

    def test_specless_or_disabled_stays_inline(self):
        runner = SimRunner(make_h100_like(seed=3))
        assert maybe_parallel_runner(runner, None) is runner
        # No RunnerSpec -> identity, even with pooling requested.
        bare = object()
        assert maybe_parallel_runner(bare, PCFG) is bare
        # Below the effective-core floor the auto heuristic opts out...
        auto = ParallelConfig(min_cores=10 ** 6)
        assert auto.resolved_workers() == 0
        assert maybe_parallel_runner(runner, auto) is runner
        # ...but an explicit worker count always pools.
        assert ParallelConfig(workers=3, min_cores=10 ** 6)
        assert ParallelConfig(workers=3,
                              min_cores=10 ** 6).resolved_workers() == 3

    def test_effective_cpu_count_sane(self):
        n = effective_cpu_count()
        assert 1 <= n <= (os.cpu_count() or 1)

    def test_worker_crash_transient_respawn_no_residue(self):
        """A killed worker costs one TransientRunnerError, nothing else."""
        cfg = ParallelConfig(workers=1, min_rows_per_shard=1)
        with ParallelPool(cfg) as crash_pool:
            prefix = crash_pool._prefix
            chaos = ChaosRunner(SimRunner(make_h100_like(seed=3)),
                                FaultSchedule(kill_worker_after=0))
            pooled = maybe_parallel_runner(chaos, cfg, pool=crash_pool)
            with pytest.raises(TransientRunnerError):
                pooled.pchase_many([("L1", 64 * KIB, 32)], 5)
            assert crash_pool.respawns == 1
            # Segment released despite the abnormal exit, pool still live.
            assert _shm_residue(prefix) == []
            clean = maybe_parallel_runner(SimRunner(make_h100_like(seed=3)),
                                          cfg, pool=crash_pool)
            rows = np.asarray(clean.pchase_many([("L1", 64 * KIB, 32)], 5))
            assert rows.shape == (1, 5)
        assert _shm_residue(prefix) == []

    def test_worker_kill_discovery_recovers_clean_topology(self):
        """Mid-round worker death -> resilience retry -> clean topology.

        The chaos schedule kills the worker process a few calls in (the
        ``MT4G_POOL_WORKER`` guard keeps the coordinator alive); the pooled
        fused discovery must converge to exactly the inline clean run —
        everything but the wall-time note, which legitimately differs.
        """
        dev = make_h100_like(seed=3)
        policy = Resilience(max_retries=4, sleep=lambda _s: None)

        def req(make_runner, **kw):
            return DiscoveryRequest(
                descriptor=sim_request_descriptor(dev, 9, None),
                vendor=dev.vendor, model=dev.name,
                backend=f"simulated:{dev.name}",
                make_runner=make_runner, n_samples=9,
                device_families=DEVICE_FAMILIES, fuse=True, **kw)

        clean, _ = discover(req(lambda: SimRunner(dev)))

        sched = FaultSchedule(kill_worker_after=6)
        shared = get_global_pool(PCFG)
        respawns0 = shared.respawns
        try:
            topo, _ = discover(req(
                lambda: ChaosRunner(SimRunner(dev), sched),
                resilience=policy, parallel=PCFG))
        finally:
            shutdown_global_pools()
        assert shared.respawns > respawns0      # kills actually happened
        assert topology_equivalent(clean, topo)
        a, b = clean.to_json(), topo.to_json()
        a.pop("notes"), b.pop("notes")
        assert a == b
        assert _shm_residue(f"mt4g{os.getpid()}") == []
