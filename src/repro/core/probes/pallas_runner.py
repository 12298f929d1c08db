"""PallasRunner: the modeled, interpret-mode ProbeRunner (CPU tests only).

The chip path is ``TpuRunner`` (``tpu_runner.py``); this runner exists so
the probe stack can be exercised end to end on a CPU.  Every probe request
executes the probe kernels from ``repro.kernels`` —
``pchase_kernel_batch`` / ``eviction_kernel_batch`` for dependent-load
chains, ``stream_read_kernel`` / ``stream_write_kernel`` for bandwidth — in
the Pallas interpreter, and the caller times the whole call (DESIGN.md
adaptation note 1).

The interpreter has no TPU memory system behind it, so the hit/miss
behavior comes from a configured ground-truth hierarchy (a ``SimDevice``
model, default ``make_pallas_model``): the modeled level an access hits
sets the *length of the dependent chain the kernel actually executes* — a
modeled miss literally serializes more loads — and the reported per-load
value comes from timing that execution.  Locations of the latency
distributions therefore track the configured hierarchy (sizes, line size,
fetch granularity are discoverable and checkable against
``model.ground_truth()``), while the distributions themselves carry real
end-to-end timing noise, which is what the K-S machinery is built to
absorb.  None of this runs on the chip (ROADMAP C3 removes it).

Shared-box drift calibration: the probe workflows compare distributions
*across* requests (a doubling step against its baseline, an eviction probe
against hit/miss references), and on a time-shared CPU the interpreter's
per-step cost drifts by tens of percent between calls — enough to fake a
regime change.  Every timed execution is therefore normalized by
**calibration launches of the identical launch** — the same buffers, grid
shape and per-row chain lengths — spread through the sample loop, so a
sample is ``modeled_cycles x (request wall / calibration wall)``.  Matching
the full launch — not just the buffer bucket — matters because the
interpreter charges a per-grid-row overhead (a 100-row sweep launch has a
very different wall-per-step than a single-row chase) and because each
chase step DMAs a 512 B row, so a buffer the host caches have not seen
runs measurably slower than one just walked: an independent calibration
buffer read that as a ~25% shift.  Only the identical launch cancels both
that overhead and temporal drift.  The result: reported
latencies land in model-cycle units comparable across requests *and
across launch shapes* — the property the planner's row classification
(every row judged against one baseline distribution) depends on.

Implementation notes:

* chase buffers are Sattolo-style single-cycle permutations sized per
  request from the probed ``SpaceInfo`` (slot i stands for byte offset
  ``i * stride``, so the resident footprint matches ``array_bytes``),
  generated vectorized (``random_cycle``) and padded to power-of-two
  buckets so the jit cache stays small;
* the chain length is passed to the kernel as data, not a static arg —
  sweeps over hundreds of sizes reuse a handful of compiled kernels;
* ``pchase_batch`` maps a whole §IV-B sweep onto the kernel grid in ONE
  launch; ``cold_chase_batch`` does the same for the §IV-D stride sweep
  with per-row chain lengths;
* the eviction-pattern probes (§IV-F/G/H) ride the same grid trick:
  ``eviction_many`` maps mixed amount/sharing/cu rows onto
  ``eviction_kernel_batch`` — each row executes a real warm-B/probe-A
  two-phase chain (Fig. 3) with both phase lengths as per-row data, and the
  calibration repeats the full two-phase launch;
* scratchpad spaces (VMEM/SMEM-like) advertise ``supports_cold=False``:
  end-to-end timing cannot classify individual loads of a cold pass there,
  and the engine registry honors the capability flag by never scheduling
  the family.  Cache-kind spaces support the cold pass through the modeled
  per-load pattern scaled by the measured per-step cost.
"""
from __future__ import annotations

import time

import numpy as np

from ..simulate import SimDevice, SimLevel
from .runners import SpaceInfo, random_cycle

__all__ = ["PallasRunner", "make_pallas_model"]

KIB = 1024


def make_pallas_model(seed: int = 0) -> SimDevice:
    """Default ground-truth hierarchy for the interpret-mode backend.

    Deliberately small (16 KiB / 64 KiB / 256 KiB) so a full discovery stays
    in seconds: interpret-mode chains cost ~70 ns per executed load, and the
    size sweeps scale with capacity.  The shape mirrors a TPU-flavored
    hierarchy: one cache-kind space in front of global loads, a
    compiler-managed VMEM scratchpad (no cold pass — capability flag), and a
    chip-level L2 ahead of device memory.
    """
    levels = [
        SimLevel("L1", 16 * KIB, 40.0, 128, 32, noise=0.0),
        SimLevel("VMEM", 64 * KIB, 12.0, 4, 4, noise=0.0, kind="scratchpad"),
        SimLevel("L2", 256 * KIB, 150.0, 128, 64, amount=1, scope="chip",
                 noise=0.0),
    ]
    return SimDevice(
        name="pallas-interp", vendor="Google", levels=levels,
        mem_latency=800.0, mem_noise=0.0,
        read_bw={}, write_bw={},        # bandwidth is measured, not modeled
        cores_per_sm=8,
        space_of_level={"global": "L1", "DeviceMemory": "L2"},
        outlier_prob=0.0,
        seed=seed,
    )


def _pow2_at_least(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 2)


class PallasRunner:
    """ProbeRunner over ``repro.kernels`` p-chase/stream kernels.

    ``base_steps`` is the minimum executed chain length per timed call: the
    jit dispatch overhead (tens of us on a CPU host) must stay small
    against the kernel's compute time for the wall-clock division to carry
    signal.  ``reps``/``cold_reps`` control how many timed executions back
    each scalar the cold-pass and bandwidth probes report.
    """

    ELEM_BYTES = 4               # int32 chase indices
    deterministic = False        # samples are real wall-time measurements

    def __init__(self, model: SimDevice | None = None, *, interpret,
                 base_steps: int = 6144, cold_reps: int = 3,
                 bandwidth_bytes: int = 1 << 21, seed: int = 0):
        # The interpreter is named, never defaulted: ``True`` (Pallas'
        # interpreter) or ``pltpu.InterpretParams()`` (TPU semantics).
        if interpret is False:
            raise ValueError("PallasRunner models its hierarchy and runs only "
                             "in the interpreter; measure a TPU with "
                             "TpuRunner (discover_pallas(interpret=False))")
        self.model = model if model is not None else make_pallas_model()
        self.base_steps = int(base_steps)
        self.cold_reps = int(cold_reps)
        self.bandwidth_bytes = int(bandwidth_bytes)
        self.interpret = interpret
        self._rng = np.random.default_rng(seed)
        self._perm_cache: dict[int, np.ndarray] = {}
        self._evictor_cache: dict[int, np.ndarray] = {}
        self._warmed: set[tuple] = set()               # launch-shape keys
        self.kernel_calls = 0
        # Eviction-grid utilization (§IV-F/G/H): dispatches vs rows carried.
        # rows > calls means heterogeneous rows actually coalesced onto
        # shared grids — the bench's ``eviction_fusion`` gate reads these.
        self.eviction_grid_calls = 0
        self.eviction_grid_rows = 0

    # ------------------------------------------------------------- spaces
    def spaces(self) -> list[SpaceInfo]:
        out = []
        for lvl in self.model.levels:
            out.append(SpaceInfo(
                name=lvl.name, scope=lvl.scope, kind=lvl.kind,
                max_bytes=lvl.size * 8,
                # Scratchpads: end-to-end timing cannot classify individual
                # cold-pass loads; the registry honors the flag.
                supports_cold=lvl.kind == "cache",
                supports_amount=lvl.kind == "cache" and lvl.scope == "core",
                supports_sharing=lvl.kind == "cache",
            ))
        return out

    # ------------------------------------------------------- chase plumbing
    def _slots(self, array_bytes: int, stride: int) -> int:
        stride_elems = max(int(stride) // self.ELEM_BYTES, 1)
        return max(int(array_bytes) // self.ELEM_BYTES // stride_elems, 4)

    def _perm(self, n: int) -> np.ndarray:
        """Single-cycle chase buffer over ``n`` slots (memoized per size)."""
        perm = self._perm_cache.get(n)
        if perm is None:
            perm = random_cycle(n, self._rng)
            self._perm_cache[n] = perm
        return perm

    def _chain_factor(self, lat_cycles: float) -> int:
        """Repetitions of the modeled latency needed to beat dispatch."""
        return max(int(np.ceil(self.base_steps / max(lat_cycles, 1.0))), 1)

    def _run_batch(self, perms: np.ndarray, steps: np.ndarray) -> float:
        """One timed launch of the grid kernel; returns wall seconds."""
        import jax.numpy as jnp

        from repro.kernels.pchase_probe import pchase_kernel_batch

        perms_j = jnp.asarray(perms)
        steps_j = jnp.asarray(steps, dtype=jnp.int32)
        t0 = time.perf_counter_ns()
        pchase_kernel_batch(perms_j, steps_j,
                            interpret=self.interpret).block_until_ready()
        self.kernel_calls += 1
        return (time.perf_counter_ns() - t0) * 1e-9

    def _stacked_perms(self, slot_counts: list[int]) -> np.ndarray:
        """(R, bucket) padded permutation matrix for a sweep's rows."""
        bucket = _pow2_at_least(max(slot_counts))
        out = np.zeros((len(slot_counts), bucket), dtype=np.int32)
        for i, n in enumerate(slot_counts):
            out[i, :n] = self._perm(n)
        return out

    def _cal_wall(self, perms: np.ndarray, steps: np.ndarray) -> float:
        """ONE wall measurement of the calibration launch: the request's own
        launch again, adjacent in time, so the request/calibration wall
        ratio cancels temporal drift, the interpreter's per-grid-row
        overhead and host-cache warmth alike (see module docstring).

        Callers combine multiple calibrations *spread across* their sample
        loops (median of a before/middle/after triple, median of adjacent
        pairs): back-to-back calibration repetitions are covered by a single
        steal-time burst together and would be no more robust than one.
        """
        return self._run_batch(perms, steps)

    def _maybe_warm(self, perms: np.ndarray, steps: np.ndarray) -> None:
        """Warm-up launch (paper §IV-A) once per (rows, bucket) grid shape.

        Chain lengths travel as data, so every launch of a seen shape hits
        the same compiled/traced kernel — re-warming would only burn a
        dispatch."""
        shape = perms.shape
        if shape not in self._warmed:
            self._run_batch(perms, steps)
            self._warmed.add(shape)

    # ------------------------------------------------------------- pchase
    def pchase(self, space, array_bytes, stride, n_samples):
        lat = self.model.hit_latency(space, array_bytes, stride)
        return self._timed_chase(array_bytes, stride, lat, int(n_samples))

    def _timed_chase(self, array_bytes, stride, lat_cycles,
                     n_samples) -> np.ndarray:
        """n_samples timed kernel executions of one modeled-latency chain.

        Each sample is the calibration-normalized per-load value
        ``lat_cycles x (c_request / c_calibration)`` — model-cycle units
        with real adjacent-in-time measurement noise.
        """
        n = self._slots(array_bytes, stride)
        m = self._chain_factor(lat_cycles)
        bucket = _pow2_at_least(n)
        perms = np.zeros((1, bucket), dtype=np.int32)
        perms[0, :n] = self._perm(n)
        steps = np.array([max(int(round(m * lat_cycles)), 1)], dtype=np.int32)
        self._maybe_warm(perms, steps)
        walls, cal = self._timed_loop(perms, steps, n_samples)
        return lat_cycles * walls / cal

    def pchase_batch(self, space, array_bytes_list, stride, n_samples):
        """A whole size sweep on the kernel grid: ONE launch per repetition.

        Row i's chain length encodes its own modeled hit latency; each timed
        launch yields one per-step cost estimate ``c`` (wall over total
        executed steps), and row i's sample for that repetition is
        ``c * lat_i`` — the same quantity ``pchase`` measures one row at a
        time, amortizing the launch overhead over the grid.
        """
        sizes = [int(ab) for ab in array_bytes_list]
        return self._timed_grid(
            [(space, ab, int(stride)) for ab in sizes], int(n_samples))

    def pchase_many(self, requests, n_samples):
        """Heterogeneous fused batch — per-row (space, array_bytes, stride)
        on ONE kernel grid (the cross-family fusion capability).

        This is what collapses the per-family kernel launches: a fusion
        round containing a size-search bisection probe, a line-size step,
        and a latency chase costs a single grid launch per repetition
        instead of one launch per family.  Row semantics are identical to
        ``pchase`` — row i's chain length encodes its own modeled hit
        latency and every repetition is calibration-normalized.
        """
        reqs = [(space, int(ab), int(stride))
                for space, ab, stride in requests]
        return self._timed_grid(reqs, int(n_samples))

    def _timed_grid(self, reqs: list[tuple], n_samples: int) -> np.ndarray:
        """Shared grid-launch timing loop behind pchase_batch/pchase_many."""
        lats = np.array([self.model.hit_latency(space, ab, stride)
                         for space, ab, stride in reqs])
        slot_counts = [self._slots(ab, stride) for _, ab, stride in reqs]
        perms = self._stacked_perms(slot_counts)
        # Spread the dispatch-beating budget over the grid: per-row chains
        # can be shorter because one launch times all of them.
        per_row = max(self.base_steps // max(len(reqs), 1), 512)
        ms = np.maximum(np.ceil(per_row / np.maximum(lats, 1.0)), 1.0)
        steps = np.asarray(np.round(ms * lats), dtype=np.int32)
        self._maybe_warm(perms, steps)
        walls, cal = self._timed_loop(perms, steps, n_samples)
        return lats[:, None] * (walls[None, :] / cal)

    def _timed_loop(self, perms: np.ndarray, steps: np.ndarray,
                    n_samples: int) -> tuple[np.ndarray, float]:
        """``n_samples`` timed request walls + a burst-resistant calibration.

        Three calibration launches INTERLEAVED with the sample loop
        (before / middle / after), combined by median: per-sample request
        noise is the distribution the statistics consume, but the
        calibration divisor scales the whole row, so no single steal
        burst may own it.  A spike on one calibration is outvoted; a
        burst long enough to cover two of the three spread-out
        calibrations covers most of the request walls as well, and then
        the ratio stays self-consistent.
        """
        cal_a = self._cal_wall(perms, steps)
        half = max(n_samples // 2, 1)
        walls = [self._run_batch(perms, steps) for _ in range(half)]
        cal_b = self._cal_wall(perms, steps)
        walls += [self._run_batch(perms, steps)
                  for _ in range(n_samples - half)]
        cal_c = self._cal_wall(perms, steps)
        return np.asarray(walls), float(np.median([cal_a, cal_b, cal_c]))

    # --------------------------------------------------------- cold chase
    def _cold_cycles(self, space, array_bytes, stride, n_loads) -> np.ndarray:
        """Modeled per-load cycle costs of a cold pass (§IV-D pattern)."""
        info = self.model.level(space)
        if info.kind != "cache":
            raise NotImplementedError(
                f"pallas runner: no cold-pass control over scratchpad "
                f"space '{space}'")
        miss = self.model.cold_miss_pattern(space, array_bytes, stride,
                                            n_loads)
        hit_lat = info.latency
        miss_lat = self.model.next_level_latency(space)
        return np.where(miss, miss_lat, hit_lat)

    def cold_chase(self, space, array_bytes, stride, n_samples):
        """Per-load cold-pass values: modeled hit/miss pattern x measured
        per-step cost of a real chain executing the modeled total work."""
        cycles = self._cold_cycles(space, array_bytes, stride, n_samples)
        return self._cold_rows([cycles])[0]

    def _cold_rows(self, cycles_rows: list[np.ndarray]) -> np.ndarray:
        """Execute + time the chains behind one or many cold rows.

        One grid launch covers every row; the per-step cost is best-of-reps
        (steal-time spikes only ever slow a run down), normalized by the
        matching best-of-reps calibration cost.  Per-load values are the
        modeled hit/miss cycle pattern scaled by that measured ratio, which
        is what the §IV-D threshold classification consumes.
        """
        totals = np.array([float(c.sum()) for c in cycles_rows])
        reps = np.maximum(np.ceil(self.base_steps / totals), 1.0)
        steps = np.asarray(np.round(reps * totals), dtype=np.int32)
        slot_counts = [max(c.size, 4) for c in cycles_rows]
        perms = self._stacked_perms(slot_counts)
        self._maybe_warm(perms, steps)
        # Cold rows are classified against an *absolute* hit/miss
        # threshold, so the whole-row scale must survive steal bursts:
        # measure ``cold_reps`` ADJACENT (request, calibration) pairs and
        # take the median per-pair ratio — a burst spanning one pair
        # inflates both walls and cancels; a spike hitting a single launch
        # is outvoted.  (min-of-requests over min-of-calibrations, by
        # contrast, lets one lucky/unlucky side skew the ratio 2x+.)
        ratios = []
        for _ in range(self.cold_reps):
            w_req = self._run_batch(perms, steps)
            ratios.append(w_req / self._cal_wall(perms, steps))
        ratio = float(np.median(ratios))
        return np.stack([ratio * cyc for cyc in cycles_rows])

    def cold_chase_batch(self, space, array_bytes_list, stride_list,
                         n_samples):
        """The §IV-D stride sweep as one grid launch (per-row strides AND
        array sizes, like the Sim backend's batch API)."""
        cycles_rows = [self._cold_cycles(space, int(ab), int(s), n_samples)
                       for ab, s in zip(array_bytes_list, stride_list)]
        return self._cold_rows(cycles_rows)

    def cold_chase_many(self, requests, n_samples):
        """Heterogeneous cold-pass fusion: per-row spaces AND strides AND
        array sizes, one grid launch for the whole round."""
        cycles_rows = [self._cold_cycles(space, int(ab), int(s), n_samples)
                       for space, ab, s in requests]
        return self._cold_rows(cycles_rows)

    # ----------------------------------------------- eviction-pattern probes
    def amount_probe(self, space, core_a, core_b, array_bytes, n_samples):
        lvl = self.model.level(space)
        lat = (self.model.next_level_latency(space)
               if self.model.amount_evicted(space, core_a, core_b,
                                            array_bytes)
               else lvl.latency)
        return self._timed_chase(array_bytes, 64, lat, int(n_samples))

    def sharing_probe(self, space_a, space_b, array_bytes, n_samples):
        lvl = self.model.level(space_a)
        lat = (self.model.next_level_latency(space_a)
               if self.model.sharing_evicted(space_a, space_b, array_bytes)
               else lvl.latency)
        return self._timed_chase(array_bytes, 64, lat, int(n_samples))

    def cu_sharing_probe(self, cu_a, cu_b, array_bytes, n_samples,
                         space="sL1d"):
        """Single §IV-H pair probe (grid path: ``eviction_many``)."""
        return self.eviction_many(
            [("cu", space, cu_a, cu_b, array_bytes)], n_samples)[0]

    def _evict_row_latency(self, req) -> tuple[float, int]:
        """(modeled post-warm probe latency, probe array bytes) of one row."""
        kind = req[0]
        if kind == "amount":
            _, space, core_a, core_b, ab = req
            evicted = self.model.amount_evicted(space, core_a, core_b, ab)
        elif kind == "sharing":
            _, space, space_b, ab = req
            evicted = self.model.sharing_evicted(space, space_b, ab)
        elif kind == "cu":
            _, space, cu_a, cu_b, ab = req
            evicted = self.model.cu_sharing_evicted(cu_a, cu_b, ab, space)
        else:
            raise ValueError(f"unknown eviction request kind: {kind!r}")
        lat = (self.model.next_level_latency(space) if evicted
               else self.model.level(space).latency)
        return lat, int(ab)

    def _evictor_perm(self, n: int) -> np.ndarray:
        """Evictor-side chase buffer: independent of the probe buffer of the
        same size (warm phase must walk a *conflicting* working set, never
        the probe array itself)."""
        perm = self._evictor_cache.get(n)
        if perm is None:
            perm = random_cycle(n, self._rng)
            self._evictor_cache[n] = perm
        return perm

    def _stacked_evictors(self, slot_counts: list[int]) -> np.ndarray:
        """(R, bucket) padded evictor matrix for an eviction grid's rows."""
        bucket = _pow2_at_least(max(slot_counts))
        out = np.zeros((len(slot_counts), bucket), dtype=np.int32)
        for i, n in enumerate(slot_counts):
            out[i, :n] = self._evictor_perm(n)
        return out

    def _run_evict(self, perms, evictors, warm, probe) -> float:
        """One timed launch of the eviction grid kernel; wall seconds."""
        import jax.numpy as jnp

        from repro.kernels.pchase_probe import eviction_kernel_batch

        t0 = time.perf_counter_ns()
        eviction_kernel_batch(
            jnp.asarray(perms), jnp.asarray(evictors),
            jnp.asarray(warm, dtype=jnp.int32),
            jnp.asarray(probe, dtype=jnp.int32),
            interpret=self.interpret).block_until_ready()
        self.kernel_calls += 1
        return (time.perf_counter_ns() - t0) * 1e-9

    def eviction_many(self, requests, n_samples):
        """Mixed §IV-F/G/H rows on ONE eviction-kernel grid per repetition.

        Each row executes the Fig. 3 pattern for real: a warm phase walks
        the row's evictor cycle once end-to-end (conflicting working set of
        the probe's footprint), then the timed probe phase walks the probe
        cycle with a chain length encoding the *modeled* post-warm hit
        level — evicted rows literally serialize more loads.  The
        calibration repeats the same two-phase launch, so the wall ratio
        cancels drift and the per-row interpreter overhead, exactly as in
        ``_timed_grid``.  Replaces one ``_timed_chase`` dispatch (~12
        launches) per amount/sharing/cu request with a single fused grid.
        """
        self.eviction_grid_calls += 1
        self.eviction_grid_rows += len(requests)
        params = [self._evict_row_latency(r) for r in requests]
        lats = np.array([lat for lat, _ in params])
        slot_counts = [self._slots(ab, 64) for _, ab in params]
        perms = self._stacked_perms(slot_counts)
        evictors = self._stacked_evictors(slot_counts)
        # One full pass over the evictor cycle: the minimal walk that
        # touches the whole conflicting footprint (and ends back at slot 0).
        warm = np.asarray(slot_counts, dtype=np.int32)
        per_row = max(self.base_steps // max(len(requests), 1), 512)
        ms = np.maximum(np.ceil(per_row / np.maximum(lats, 1.0)), 1.0)
        probe = np.asarray(np.round(ms * lats), dtype=np.int32)
        shape_key = ("evict", perms.shape, evictors.shape)
        if shape_key not in self._warmed:
            self._run_evict(perms, evictors, warm, probe)
            self._warmed.add(shape_key)
        args = (perms, evictors, warm, probe)
        cal_a = self._run_evict(*args)
        half = max(int(n_samples) // 2, 1)
        walls = [self._run_evict(*args) for _ in range(half)]
        cal_b = self._run_evict(*args)
        walls += [self._run_evict(*args)
                  for _ in range(int(n_samples) - half)]
        cal_c = self._run_evict(*args)
        cal = float(np.median([cal_a, cal_b, cal_c]))
        return lats[:, None] * (np.asarray(walls)[None, :] / cal)

    # ---------------------------------------------------------- bandwidth
    def bandwidth(self, space, mode="read"):
        """Stream-kernel bandwidth: bytes moved over best-of-reps wall time.

        Interpret-mode numbers characterize the host CPU running the
        interpreter, not a TPU.
        """
        import jax.numpy as jnp

        from repro.kernels.stream_probe import (stream_read_kernel,
                                                stream_write_kernel)

        del space  # one DMA path in interpret mode
        cols = 1024
        rows = max(self.bandwidth_bytes // (4 * cols), 1)
        block_rows = min(64, rows)
        rows = (rows // block_rows) * block_rows
        x = jnp.arange(rows * cols, dtype=jnp.float32).reshape(rows, cols)
        fn = stream_read_kernel if mode == "read" else stream_write_kernel
        fn(x, block_rows=block_rows,
           interpret=self.interpret).block_until_ready()
        best = np.inf
        for _ in range(self.cold_reps):
            t0 = time.perf_counter_ns()
            fn(x, block_rows=block_rows,
               interpret=self.interpret).block_until_ready()
            best = min(best, time.perf_counter_ns() - t0)
            self.kernel_calls += 1
        moved = x.size * 4 * (2 if mode == "write" else 1)
        return moved / (best * 1e-9)

    # ------------------------------------------------------------- hooks
    def api_size(self, space: str) -> int | None:
        try:
            return self.model.level(space).size
        except KeyError:
            return None

    def cu_ids(self) -> list[int]:
        return sorted(cu for grp in self.model.cu_share_groups for cu in grp)

    @property
    def cores_per_sm(self) -> int:
        return self.model.cores_per_sm

    def ground_truth(self) -> dict[str, dict]:
        return self.model.ground_truth()
