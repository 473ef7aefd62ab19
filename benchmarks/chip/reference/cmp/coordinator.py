"""CBP coordination mechanism (paper §3.3, Figs. 6-8).

The coordinator owns the three local controllers and runs the Fig. 8
timeline against a *plant* — anything that can execute an interval under an
allocation and report :class:`~repro.core.types.IntervalStats`.  Two plants
exist in this repo: the 16-core CMP interval model (``repro.sim.runner``,
faithful reproduction) and the TPU runtime knob binding
(``repro.runtime.cbp_runtime``).

Controller prioritization (paper §3.3): cache first ("avoiding a memory
access is typically more effective than lowering the memory access
penalty"), then bandwidth, then prefetch ("the prefetcher setting is
determined based on the current allocation of cache and bandwidth").

Inter-controller feedback is implicit in the measurement loop, exactly as in
the paper: the bandwidth controller sees queuing delays that already reflect
the cache allocation (#1) and prefetch misses (#2); prefetch A/B samples run
under the current cache+bandwidth allocation (#3, #4); the ATD counters see
prefetch hits, shrinking the next cache allocation for prefetch-friendly
clients (#5).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Protocol

import numpy as np

from .atd import SampledATD
from .bandwidth_controller import BandwidthController
from .cache_controller import CacheController
from .prefetch_controller import PrefetchController
from .types import (
    Allocation,
    CBPParams,
    IntervalStats,
    Mode,
    PrefetchMode,
    ScheduleConfigError,
)


class Plant(Protocol):
    """What the coordinator manages.

    ``allocator_backend`` selects where the Lookahead cache allocator runs
    ("numpy" host reference | "jax" batched device greedy); consumers read
    it with a "numpy" fallback, so a plant that omits it still works but
    silently stays on the host path — declare it explicitly.
    """

    n_clients: int
    total_cache_units: int
    total_bandwidth: float
    allocator_backend: str

    def run_interval(self, alloc: Allocation,
                     duration_ms: float) -> IntervalStats:
        """Execute ``duration_ms`` under ``alloc`` and report observations."""
        ...


@dataclasses.dataclass
class IntervalRecord:
    t_ms: float
    duration_ms: float
    alloc: Allocation
    stats: IntervalStats


@dataclasses.dataclass(frozen=True)
class ScheduleSegment:
    """One segment of the Fig. 8 timeline.

    ``kind`` is one of ``"reconfigure"`` (zero-duration boundary where the
    cache/bandwidth controllers fire), ``"sample_off"`` / ``"sample_on"``
    (the prefetch A/B sampling periods), and ``"run"`` (the remainder of the
    reconfiguration interval under the decided allocation).
    """

    kind: str
    duration_ms: float


def fig8_schedule(total_ms: float, params: CBPParams,
                  prefetch_dynamic: bool) -> List[ScheduleSegment]:
    """The Fig. 8 timeline as data, shared by every coordinator.

    Both :class:`CBPCoordinator` (one plant at a time) and the batched sweep
    coordinator (``repro.sim.sweep``) execute exactly this segment list, so
    the scalar and batched paths cannot drift apart on scheduling.  The
    non-boundary durations sum exactly to ``total_ms`` whenever each
    reconfiguration interval can contain its sampling overhead (see
    ``tests/test_coordinator_timeline.py``).

    :class:`~repro.core.types.CBPParams` rejects configurations whose
    sampling overhead exceeds the interval at construction; the check is
    repeated here because params are mutable dataclasses and a drifted
    schedule is silent otherwise.
    """
    if prefetch_dynamic and (params.reconfiguration_interval_ms
                             < 2.0 * params.prefetch_sampling_period_ms):
        raise ScheduleConfigError(
            "reconfiguration_interval_ms "
            f"({params.reconfiguration_interval_ms!r}) < 2 * "
            "prefetch_sampling_period_ms "
            f"({params.prefetch_sampling_period_ms!r}): the sampling "
            "overhead does not fit in the interval, so the 'run' segment "
            "would be dropped and reconfigure boundaries would drift")
    segments: List[ScheduleSegment] = []
    t = 0.0
    first = True
    while t < total_ms - 1e-9:
        if not first:
            segments.append(ScheduleSegment("reconfigure", 0.0))
        sampled = 0.0
        if prefetch_dynamic:
            p = params.prefetch_sampling_period_ms
            segments.append(ScheduleSegment("sample_off", p))
            segments.append(ScheduleSegment("sample_on", p))
            sampled = 2.0 * p
            t += sampled
        remain = min(params.reconfiguration_interval_ms - sampled,
                     total_ms - t)
        if remain > 0:
            segments.append(ScheduleSegment("run", remain))
            t += remain
        first = False
    return segments


class CBPCoordinator:
    """Dynamically manage cache, bandwidth and prefetch (paper Fig. 8).

    ``cache_mode`` / ``bandwidth_mode`` / ``prefetch_mode`` select the
    Table-3 resource-manager family; CBP proper is (DYNAMIC, DYNAMIC,
    DYNAMIC).  Subset managers (e.g. ``cache+pref``) reuse the same loop
    with the unmanaged resource pinned, which is how the paper's comparison
    configurations are built.
    """

    def __init__(
        self,
        plant: Plant,
        params: Optional[CBPParams] = None,
        cache_mode: Mode = Mode.DYNAMIC,
        bandwidth_mode: Mode = Mode.DYNAMIC,
        prefetch_mode: PrefetchMode = PrefetchMode.DYNAMIC,
    ):
        self.plant = plant
        self.params = params or CBPParams()
        self.cache_mode = cache_mode
        self.bandwidth_mode = bandwidth_mode
        self.prefetch_mode = prefetch_mode

        n = plant.n_clients
        self.atd = SampledATD(n, plant.total_cache_units)
        # Allocation is backend-dispatched: plants that keep their model on
        # device (CMPConfig(backend="jax")) also keep the Lookahead greedy
        # there (repro.core.cache_controller_jax, bit-parity tested).
        self.cache_ctl = CacheController(
            plant.total_cache_units, self.params.min_ways,
            backend=getattr(plant, "allocator_backend", "numpy"))
        self.bw_ctl = BandwidthController(
            plant.total_bandwidth, self.params.min_bandwidth_allocation,
            decay=self.params.bandwidth_delay_decay)
        self.pf_ctl = PrefetchController(n, self.params.speedup_threshold)
        self.history: List[IntervalRecord] = []
        self._t_ms = 0.0

        # Step 0 (Fig. 8): equal partitions, no miss/delay info yet.
        self.alloc = self._initial_allocation()

    # ------------------------------------------------------------------ #

    def _initial_allocation(self) -> Allocation:
        n = self.plant.n_clients
        units = np.full(n, self.plant.total_cache_units // n, dtype=np.int64)
        units[: self.plant.total_cache_units - int(units.sum())] += 1
        bw = np.full(n, self.plant.total_bandwidth / n, dtype=np.float64)
        pf = np.full(n, self.prefetch_mode == PrefetchMode.ON, dtype=bool)
        return Allocation(
            cache_units=units,
            bandwidth=bw,
            prefetch_on=pf,
            cache_mode=self.cache_mode,
            bandwidth_mode=self.bandwidth_mode,
        )

    def _run(self, alloc: Allocation, duration_ms: float,
             record: bool = True) -> IntervalStats:
        stats = self.plant.run_interval(alloc, duration_ms)
        self.atd.record(stats.utility_curves * (duration_ms / 1.0))
        self.bw_ctl.observe(stats.queuing_delay_ns * duration_ms)
        if record:
            self.history.append(
                IntervalRecord(self._t_ms, duration_ms, alloc.copy(), stats))
        self._t_ms += duration_ms
        return stats

    def _reconfigure(self) -> None:
        """Reconfiguration boundary: cache -> bandwidth (priority order)."""
        if self.cache_mode == Mode.DYNAMIC:
            # Interaction #5: the utility curves already include prefetch
            # hits, so prefetch-friendly clients present flatter curves and
            # receive less cache.
            self.alloc.cache_units = self.cache_ctl.allocate(
                self.atd.utility_curves())
        self.atd.halve(self.params.atd_decay)
        if self.bandwidth_mode == Mode.DYNAMIC:
            # Interactions #1/#2: delays reflect cache allocation and
            # prefetch misses of the prior interval.
            self.alloc.bandwidth = self.bw_ctl.allocate()

    # ------------------------------------------------------------------ #

    def run(self, total_ms: float) -> List[IntervalRecord]:
        """Run the Fig. 8 timeline for ``total_ms``.

        The A/B samples run under the *current* cache+bandwidth allocation —
        interactions #3/#4.
        """
        n = self.plant.n_clients
        stats_off: Optional[IntervalStats] = None
        schedule = fig8_schedule(
            total_ms, self.params,
            self.prefetch_mode == PrefetchMode.DYNAMIC)
        for seg in schedule:
            if seg.kind == "reconfigure":     # Steps 2-3
                self._reconfigure()
            elif seg.kind == "sample_off":    # Step 1/4
                off = self.alloc.copy()
                off.prefetch_on = np.zeros(n, dtype=bool)
                stats_off = self._run(off, seg.duration_ms)
            elif seg.kind == "sample_on":
                on = self.alloc.copy()
                on.prefetch_on = np.ones(n, dtype=bool)
                stats_on = self._run(on, seg.duration_ms)
                self.alloc.prefetch_on = self.pf_ctl.update(
                    stats_on.ipc, stats_off.ipc)
            else:
                self._run(self.alloc, seg.duration_ms)
        return self.history

    # Aggregation helpers ------------------------------------------------ #

    def mean_ipc(self) -> np.ndarray:
        """Time-weighted mean performance per client over the run."""
        total = np.zeros(self.plant.n_clients)
        t = 0.0
        for rec in self.history:
            total += rec.stats.ipc * rec.duration_ms
            t += rec.duration_ms
        return total / max(t, 1e-12)
