"""Fault-tolerant discovery suite (chaos -> retry -> degrade -> resume).

Covers the resilience layer end to end against chaos-injected runners:

* scheduler-level transient retry with capped backoff, and graceful
  degradation past the budget (unfused and fused paths);
* engine-level degradation: a family past its retry budget lands as an
  ``"unknown"`` attribute with ``degraded`` provenance instead of
  aborting, and dependents keep working;
* the reliability headline: a discovery under a value-preserving
  transient fault schedule is ``topology_equivalent`` to the clean run;
* checkpoint/resume: an interrupted discovery resumes from the persisted
  sample-cache checkpoint with ZERO re-probed rows (exact miss
  arithmetic), including through a ``JobEngine`` retry;
* the statistical hardening knobs (MAD gating, confidence-driven
  resampling) and the promoted ``core.errors`` taxonomy.
"""
import numpy as np
import pytest

from repro.core import make_h100_like
from repro.core.discover import (DiscoveryRequest, discover, discover_sim,
                                 sim_request_descriptor)
from repro.core.engine.cache import CachingRunner, SampleCache
from repro.core.engine.fusion import FusionDispatcher, run_fused
from repro.core.engine.scheduler import WorkItem, run_work_items
from repro.core.engine.store import TopologyStore, request_key
from repro.core.errors import DegradedResult, Resilience, TransientRunnerError
from repro.core.probes import ChaosRunner, FaultSchedule, SimRunner
from repro.core.probes.size import ShiftClassifier, find_size
from repro.core.stats import mad_gate
from repro.core.topology import PROVENANCE_DEGRADED, topology_equivalent

KIB = 1024
DEVICE_FAMILIES = ("sharing", "device_memory_latency",
                   "device_memory_bandwidth")


def h100_runner():
    return SimRunner(make_h100_like(seed=3))


def no_sleep_resilience(**kw):
    kw.setdefault("max_retries", 3)
    return Resilience(sleep=lambda _s: None, **kw)


def make_request(make_runner, resilience, n_samples=9):
    dev = make_h100_like(seed=3)
    return DiscoveryRequest(
        descriptor=sim_request_descriptor(dev, n_samples, None,
                                          resilience=resilience),
        vendor=dev.vendor, model=dev.name,
        backend=f"simulated:{dev.name}",
        make_runner=make_runner, n_samples=n_samples,
        device_families=DEVICE_FAMILIES, resilience=resilience)


# --------------------------------------------------------------------------
# Scheduler-level retry / degradation (synthetic work items)
# --------------------------------------------------------------------------
class TestSchedulerRetry:
    def _flaky_item(self, fail_times, key="a"):
        """A work item that raises TransientRunnerError ``fail_times`` times
        before returning; counts its invocations."""
        calls = {"n": 0}

        def fn(_results):
            calls["n"] += 1
            if calls["n"] <= fail_times:
                raise TransientRunnerError(f"flake #{calls['n']}")
            return f"{key}-ok"

        return WorkItem(key=key, fn=fn), calls

    @pytest.mark.parametrize("failures", [1, 2])
    def test_transient_retried_to_success(self, failures):
        it, calls = self._flaky_item(failures)
        res = run_work_items([it], resilience=no_sleep_resilience())
        assert res.results["a"] == "a-ok"
        assert calls["n"] == failures + 1
        assert res.retries == failures
        assert res.degraded == []

    def test_backoff_schedule_capped(self):
        sleeps = []
        policy = Resilience(max_retries=4, backoff_base_s=1.0,
                            backoff_cap_s=3.0, sleep=sleeps.append)
        it, _ = self._flaky_item(4)
        run_work_items([it], resilience=policy)
        assert sleeps == [1.0, 2.0, 3.0, 3.0]   # doubling, then the cap

    def test_exhaustion_degrades_via_on_exhausted(self):
        it, calls = self._flaky_item(99)
        seen = []

        def on_exhausted(item, exc, attempts):
            seen.append((item.key, str(exc), attempts))
            return "degraded-stand-in"

        res = run_work_items([it],
                             resilience=no_sleep_resilience(max_retries=2),
                             on_exhausted=on_exhausted)
        assert res.results["a"] == "degraded-stand-in"
        assert res.degraded == ["a"]
        assert calls["n"] == 3                   # 1 try + 2 retries
        assert seen == [("a", "flake #3", 3)]

    def test_exhaustion_without_degrade_raises(self):
        it, _ = self._flaky_item(99)
        with pytest.raises(TransientRunnerError):
            run_work_items([it], resilience=no_sleep_resilience(
                max_retries=1, degrade=False))

    def test_no_policy_means_no_retry(self):
        it, calls = self._flaky_item(1)
        with pytest.raises(TransientRunnerError):
            run_work_items([it])
        assert calls["n"] == 1

    def test_non_transient_never_retried(self):
        calls = {"n": 0}

        def fn(_results):
            calls["n"] += 1
            raise ValueError("deterministic bug")

        with pytest.raises(ValueError):
            run_work_items([WorkItem(key="a", fn=fn)],
                           resilience=no_sleep_resilience())
        assert calls["n"] == 1

    def test_on_item_done_fires_per_completed_item(self):
        done = []
        items = [WorkItem(key="a", fn=lambda _r: 1),
                 WorkItem(key="b", fn=lambda _r: 2, deps=("a",))]
        run_work_items(items, on_item_done=done.append)
        assert done == ["a", "b"]


# --------------------------------------------------------------------------
# Fused-mode fault handling (split rounds + item restart)
# --------------------------------------------------------------------------
class TestFusedFaults:
    def _fused_pchase_items(self, dispatcher, sizes):
        proxy = dispatcher.proxy()
        return [
            WorkItem(key=f"p{i}",
                     fn=lambda _r, s=s: proxy.pchase("L1", s, 32, 9))
            for i, s in enumerate(sizes)
        ]

    def test_batch_fault_splits_round_per_row(self):
        """A fused dispatch that faults must be split into single-row
        retries — untouched items keep their results, nothing aborts."""
        sched = FaultSchedule(seed=2, permanent_kinds=("pchase_many",))
        cached = CachingRunner(ChaosRunner(h100_runner(), sched),
                               cache=SampleCache())
        dispatcher = FusionDispatcher(cached)
        sizes = [8 * KIB, 16 * KIB, 24 * KIB]
        out = run_fused(self._fused_pchase_items(dispatcher, sizes),
                        dispatcher)
        assert dispatcher.split_rounds >= 1
        base = h100_runner()
        for i, s in enumerate(sizes):
            assert np.array_equal(out.results[f"p{i}"],
                                  base.pchase("L1", s, 32, 9))

    def test_single_row_transient_restarts_item(self):
        """When the split fallback itself faults, the owning item restarts
        under the policy and converges once the fault budget is spent."""
        sched = FaultSchedule(seed=5, transient_rate=1.0,
                              batch_fault_rate=1.0,
                              max_faults_per_request=1)
        cached = CachingRunner(ChaosRunner(h100_runner(), sched),
                               cache=SampleCache())
        dispatcher = FusionDispatcher(cached)
        sizes = [8 * KIB, 16 * KIB]
        out = run_fused(self._fused_pchase_items(dispatcher, sizes),
                        dispatcher, resilience=no_sleep_resilience())
        assert out.retries >= 1
        assert dispatcher.split_rounds >= 1
        base = h100_runner()
        for i, s in enumerate(sizes):
            assert np.array_equal(out.results[f"p{i}"],
                                  base.pchase("L1", s, 32, 9))

    def test_fused_exhaustion_degrades(self):
        sched = FaultSchedule(seed=5, permanent_kinds=("pchase",
                                                       "pchase_many"))
        cached = CachingRunner(ChaosRunner(h100_runner(), sched),
                               cache=SampleCache())
        dispatcher = FusionDispatcher(cached)
        out = run_fused(
            self._fused_pchase_items(dispatcher, [8 * KIB]), dispatcher,
            resilience=no_sleep_resilience(max_retries=1),
            on_exhausted=lambda it, exc, attempts: ("degraded", attempts))
        assert out.degraded == ["p0"]
        assert out.results["p0"] == ("degraded", 2)


# --------------------------------------------------------------------------
# Discovery-level behavior (the acceptance criteria)
# --------------------------------------------------------------------------
class TestResilientDiscovery:
    @pytest.fixture(scope="class")
    def clean(self):
        return discover_sim(make_h100_like(seed=3), n_samples=9)

    def test_transient_faults_yield_equivalent_topology(self, clean):
        """The headline contract: under a value-preserving transient fault
        schedule, retries reproduce the clean topology exactly."""
        sched = FaultSchedule(seed=11, transient_rate=0.05,
                              max_faults_per_request=1)
        holder = {}

        def mk():
            holder["r"] = ChaosRunner(h100_runner(), sched)
            return holder["r"]

        topo, timings = discover(make_request(mk, no_sleep_resilience()))
        assert holder["r"].faults_injected > 0   # chaos actually fired
        assert topology_equivalent(clean[0], topo, rel_tol=1e-6)
        meta = timings.meta["resilience"]
        assert meta["retries"] >= holder["r"].faults_injected
        assert meta["degraded"] == []

    def test_permanent_fault_degrades_not_aborts(self, clean):
        sched = FaultSchedule(seed=7, permanent_kinds=("bandwidth",))
        topo, timings = discover(make_request(
            lambda: ChaosRunner(h100_runner(), sched),
            no_sleep_resilience(max_retries=1)))
        degraded = timings.meta["resilience"]["degraded"]
        assert "L2/bandwidth" in degraded
        l2 = topo.find_memory("L2")
        attr = l2.attrs["read_bw"]
        assert attr.value == "unknown"
        assert attr.provenance == PROVENANCE_DEGRADED
        assert attr.confidence == 0.0
        # unaffected families still measured normally
        assert l2.get("size") == clean[0].find_memory("L2").get("size")
        assert any("degraded after" in n for n in topo.notes)

    def test_degraded_breaks_equivalence(self, clean):
        """Degradation must be *visible*: a degraded topology is NOT
        equivalent to the clean one (provenance is part of the contract)."""
        sched = FaultSchedule(seed=7, permanent_kinds=("bandwidth",))
        topo, _ = discover(make_request(
            lambda: ChaosRunner(h100_runner(), sched),
            no_sleep_resilience(max_retries=1)))
        assert not topology_equivalent(clean[0], topo, rel_tol=1e-6)

    def test_without_policy_transients_propagate(self):
        sched = FaultSchedule(seed=11, transient_rate=1.0,
                              max_faults_per_request=10)
        with pytest.raises(TransientRunnerError):
            discover(make_request(
                lambda: ChaosRunner(h100_runner(), sched), None))


class TestCheckpointResume:
    def _interrupt_then_resume(self, tmp_path):
        clean_topo, clean_t = discover_sim(make_h100_like(seed=3),
                                           n_samples=9)
        clean_misses = clean_t.meta["cache"]["misses"]

        store = TopologyStore(str(tmp_path / "store"))
        policy = no_sleep_resilience()
        holder = {}

        def mk_killed():
            holder["r"] = ChaosRunner(h100_runner(),
                                      FaultSchedule(seed=5, kill_after=40))
            return holder["r"]

        with pytest.raises(RuntimeError, match="chaos kill"):
            discover(make_request(mk_killed, policy), store=store)

        key = request_key(make_request(h100_runner, policy).descriptor)
        ckpt = store.load_checkpoint(key)
        assert ckpt is not None
        entries, families = ckpt
        assert entries and families

        resumed, t = discover(make_request(h100_runner, policy),
                              store=store)
        assert t.meta["resume"] == {"rows": len(entries),
                                    "families_done": len(families)}
        assert t.meta["cache"]["misses"] + len(entries) == clean_misses
        assert topology_equivalent(clean_topo, resumed, rel_tol=1e-6)
        assert not store.has_checkpoint(key)
        # the finished run persisted: a third call is a pure store hit
        _, t3 = discover(make_request(h100_runner, policy), store=store)
        assert "cache" not in t3.meta

    def test_interrupt_then_resume_zero_recompute(self, tmp_path):
        """Kill a discovery mid-run; the rerun must (a) preload the
        checkpoint, (b) re-probe ZERO persisted rows (exact miss
        arithmetic), (c) produce the equivalent topology, (d) clear the
        spent checkpoint."""
        self._interrupt_then_resume(tmp_path)

    @pytest.mark.parametrize("cores", [1, 8, 64])
    def test_checkpoint_resume_independent_of_core_count(self, tmp_path,
                                                         monkeypatch, cores):
        """The kill/resume contract holds whatever the machine's core
        count: the schedule is the same on every machine, so the kill
        lands at the same row and the checkpoint holds every row probed
        before it."""
        from repro.core.engine import parallel

        monkeypatch.setattr(parallel, "effective_cpu_count", lambda: cores)
        self._interrupt_then_resume(tmp_path)

    def test_checkpoint_is_per_request_key(self, tmp_path):
        """Different request descriptors never share a checkpoint."""
        store = TopologyStore(str(tmp_path / "store"))
        policy = no_sleep_resilience()

        def mk():
            return ChaosRunner(h100_runner(),
                               FaultSchedule(seed=5, kill_after=40))

        with pytest.raises(RuntimeError):
            discover(make_request(mk, policy), store=store)
        key9 = request_key(make_request(mk, policy).descriptor)
        key7 = request_key(make_request(mk, policy,
                                        n_samples=7).descriptor)
        assert store.has_checkpoint(key9)
        assert not store.has_checkpoint(key7)

    def test_job_engine_retry_resumes_from_checkpoint(self, tmp_path,
                                                      monkeypatch):
        """The serve path: attempt 1 dies on an escaped transient fault
        (engine retry disabled), the JobEngine's capped retry reruns the
        request, and attempt 2 resumes from the checkpoint — probing far
        fewer rows than attempt 1 did."""
        from repro.serve import jobs as jobs_module
        from repro.serve.jobs import JobEngine

        store = TopologyStore(str(tmp_path / "store"))
        # No engine-level retries: the first TransientRunnerError escapes
        # discover(), leaving the checkpoint for the job retry to consume.
        policy = Resilience(max_retries=0, degrade=False,
                            sleep=lambda _s: None)
        # ONE chaos runner across attempts: its per-request fault budget
        # makes attempt 1 fail and attempt 2's retry of the same request
        # succeed (faults are spent, not random).
        chaos = ChaosRunner(h100_runner(),
                            FaultSchedule(seed=23, transient_rate=0.02,
                                          max_faults_per_request=1))
        # dispatch count of a full, clean, storeless run — the work a
        # non-resuming retry would pay every time
        probe = ChaosRunner(h100_runner())
        discover(make_request(lambda: probe, policy))
        full_calls = probe.calls
        calls_per_attempt = []
        timings_seen = []

        def run():
            before = chaos.calls
            try:
                topo, timings = discover(make_request(lambda: chaos,
                                                      policy), store=store)
                timings_seen.append(timings)
                return topo, timings
            finally:
                calls_per_attempt.append(chaos.calls - before)

        request = make_request(lambda: chaos, policy)

        def fake_resolve(params, _store, parallel=None):
            return request.descriptor, request_key(request.descriptor), run

        monkeypatch.setattr(jobs_module, "resolve_discovery", fake_resolve)
        engine = JobEngine(store, workers=1, max_retries=2,
                           sleep=lambda _s: None).start()
        try:
            job, created = engine.submit({"backend": "sim",
                                          "device": "h100"})
            assert created
            engine.wait(job.job_id, timeout_s=120)
        finally:
            engine.stop()
        assert job.state == "done", job.error
        assert job.attempts >= 2            # >= one job-level retry happened
        assert chaos.faults_injected >= 1
        # resume did real work-saving: every attempt (failed early OR
        # resumed from the checkpoint) dispatched fewer probes than a full
        # from-scratch run would have
        assert len(calls_per_attempt) == job.attempts
        assert all(c < full_calls for c in calls_per_attempt)
        # ...and the successful attempt really did preload the checkpoint
        assert timings_seen[-1].meta["resume"]["rows"] > 0
        assert not store.has_checkpoint(job.key)
        assert store.get(job.key) is not None


# --------------------------------------------------------------------------
# Statistical hardening: MAD gating + confidence-driven resampling
# --------------------------------------------------------------------------
class TestStatisticalHardening:
    def test_mad_gate_drops_spike_keeps_body(self):
        rng = np.random.default_rng(0)
        body = rng.normal(100.0, 3.0, 64)
        spiked = np.concatenate([body, [800.0]])     # 8x throttle spike
        gated = mad_gate(spiked, k=5.0)
        assert gated.size == 64
        assert gated.max() < 800.0

    def test_mad_gate_no_ops(self):
        short = np.array([1.0, 2.0, 900.0])
        assert np.array_equal(mad_gate(short), short)      # too short
        const = np.full(16, 7.0)
        assert np.array_equal(mad_gate(const), const)      # zero MAD

    def test_classifier_default_unchanged_by_knobs_off(self):
        rng = np.random.default_rng(1)
        base = rng.normal(100.0, 3.0, 33)
        cur = rng.normal(160.0, 3.0, 33)
        assert ShiftClassifier(base, 0.01, 0.15).shifted(cur)
        assert not ShiftClassifier(base, 0.01, 0.15).shifted(
            rng.normal(100.0, 3.0, 33))

    def test_mad_gating_suppresses_outlier_false_shift(self):
        """A clean row contaminated with throttle spikes must NOT classify
        as shifted once MAD gating is on — and DOES without it (same data,
        same test), proving the gate is what saves the verdict."""
        rng = np.random.default_rng(2)
        base = rng.normal(100.0, 2.0, 96)
        cur = rng.normal(100.0, 2.0, 96)
        cur[:29] = 800.0                  # ~30% throttle-spike contamination
        assert ShiftClassifier(base, 0.01, 0.0).shifted(cur.copy())
        assert not ShiftClassifier(base, 0.01, 0.0,
                                   mad_k=5.0).shifted(cur.copy())

    def test_ambiguous_verdict_triggers_resample(self):
        rng = np.random.default_rng(3)
        base = rng.normal(100.0, 3.0, 33)
        clf = ShiftClassifier(base, 0.01, 0.15, resample_band=1.0)
        called = {"n": 0}

        def resample():
            called["n"] += 1
            return rng.normal(100.0, 3.0, 33)

        # band=1.0 makes EVERY verdict ambiguous -> resample always fires
        clf.shifted(rng.normal(100.0, 3.0, 33), resample=resample)
        assert called["n"] == 1

    def test_find_size_robust_matches_dense_on_clean_runner(self):
        """On a clean runner the hardened dense path must find the same
        boundary as the historical dense path (defaults bit-identical;
        knobs only matter under contamination)."""
        runner = h100_runner()
        plain = find_size(runner, "L1", n_samples=17)
        hard = find_size(runner, "L1", n_samples=17,
                         robust=Resilience(mad_k=5.0, resample_band=0.02,
                                           resample_extra=9))
        assert plain.found and hard.found
        assert hard.size == plain.size


# --------------------------------------------------------------------------
# The promoted error taxonomy
# --------------------------------------------------------------------------
class TestErrorTaxonomy:
    def test_transient_error_single_class(self):
        import repro.serve
        import repro.serve.jobs
        from repro import core

        assert (repro.serve.TransientRunnerError
                is repro.serve.jobs.TransientRunnerError
                is core.TransientRunnerError
                is TransientRunnerError)

    def test_resilience_descriptor_entry(self):
        assert Resilience().descriptor_entry() is None
        assert Resilience(max_retries=9).descriptor_entry() is None
        entry = Resilience(mad_k=5.0, resample_band=0.02,
                           resample_extra=9).descriptor_entry()
        assert entry == {"mad_k": 5.0, "resample_band": 0.02,
                         "resample_extra": 9}

    def test_statistical_knobs_key_the_descriptor(self):
        dev = make_h100_like(seed=3)
        base = sim_request_descriptor(dev, 9, None)
        retry_only = sim_request_descriptor(
            dev, 9, None, resilience=Resilience(max_retries=7))
        hardened = sim_request_descriptor(
            dev, 9, None, resilience=Resilience(mad_k=5.0))
        assert request_key(base) == request_key(retry_only)
        assert request_key(base) != request_key(hardened)

    def test_degraded_result_ducks_as_not_found(self):
        dr = DegradedResult(family="size", key="L1/size", error="boom",
                            attempts=3)
        assert dr.found is False

    def test_backoff_formula(self):
        r = Resilience(backoff_base_s=0.5, backoff_cap_s=2.0)
        assert [r.backoff(i) for i in range(4)] == [0.5, 1.0, 2.0, 2.0]
