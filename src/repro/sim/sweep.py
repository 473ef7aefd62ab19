"""Batched Table-3 sweep runner (the Figs. 9-12 evaluation substrate).

The paper's headline results come from running the Table-3 resource-manager
configurations over dozens of 16-core workload mixes.  The scalar path
(:func:`repro.sim.managers.run_all_managers`) evaluates one (mix, manager)
pair at a time; this module stacks all mixes along a leading batch axis and
drives the jitted JAX interval model (:mod:`repro.sim.memsys_jax`), so each
timeline segment of each manager is ONE device call covering every mix —
no Python loop ever calls ``memsys.evaluate`` per (mix, manager) pair.

Since PR 2 the Lookahead cache allocator is batched too
(:mod:`repro.core.cache_controller_jax`): every reconfiguration boundary is
one jitted device call over all mixes, so a full sweep performs **zero**
per-mix host allocator calls (assert with
:func:`repro.core.allocator_calls`).  CPpf's friendly-mask allocation is
vectorized the same way (`CacheController.allocate_masked`).

Since PR 3 the whole Fig. 8 timeline of each manager is ONE jitted device
program (:mod:`repro.sim.timeline_jax`): the bandwidth controller and the
prefetch throttle run inside the scan next to the batched Lookahead
allocator, so no segment returns to the host.
Since PR 5 the *manager axis* is batched too: every Table-3 manager's
segment table and knob flags stack along a leading axis inside one
program (:func:`repro.sim.timeline_jax.run_timelines`), so a full sweep
calls TWO jitted entries — the stacked manager set plus the shared
baseline evaluation (counter: :func:`repro.core.device_dispatches`) — and
the 2-D (manager, mix) grid shards across devices via
:func:`repro.distributed.shard_grid`.  The counter counts those entries
only: collecting the results slices each manager's fields out of the
stacked outputs with small eager programs and fetches each field with a
transfer of its own, which a profiler trace shows inside the sweep's
``cbp.sweep.collect`` span (:func:`_run_sweep_one`).  The
PR 3/4 one-program-per-manager path survives as
``CMPConfig(timeline_backend="fused")`` (the stacking parity reference —
bit-identical per-(manager, mix) results), the PR 2 per-segment host
loop as ``CMPConfig(timeline_backend="segment")`` (parity/debug).

Structure:

* :class:`BatchedCMPPlant` — the CMP interval model over M stacked mixes;
  ``run_interval`` takes (M, n) allocation arrays and returns (M, n) stats.
* :class:`BatchedCoordinator` — :class:`~repro.core.CBPCoordinator`
  vectorized over the mix axis.  It executes exactly the same
  :func:`~repro.core.fig8_schedule` segment list (fused into one program
  by default), so scalar and batched trajectories cannot drift apart on
  scheduling.  ``params_rows`` lets each batch row carry its own
  non-schedule ``CBPParams`` (min_ways, speedup_threshold,
  min_bandwidth_allocation, atd_decay, bandwidth_delay_decay), which is
  how ``param_grid`` sweeps batch the Fig. 12 design space.
* :func:`run_sweep` — evaluate a set of managers over a set of mixes (and
  optionally a leading ``CBPParams`` axis via ``param_grid=``); returns a
  :class:`SweepResult` with per-mix IPC, weighted speedup and ANTT against
  the shared unpartitioned baseline.

Parity contract: with the same mixes and parameters, per-mix results match
the scalar numpy path up to the 1e-5 model tolerance (and bit-identical
controller decisions away from knife-edges) — see ``tests/test_sim_sweep.py``,
``tests/test_timeline_fused.py`` and ``tests/test_cache_controller_jax.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import (
    Allocation,
    BandwidthController,
    CacheController,
    CBPParams,
    Mode,
    PrefetchMode,
    ScheduleSegment,
    fig8_schedule,
    throttle_decision,
)
from repro.core.dispatch import span
from repro.core.types import IntervalStats
from repro.sim import memsys, memsys_jax, policies, timeline_jax
from repro.sim.apps import AppArrays, stack_mixes
from repro.sim.managers import MANAGER_NAMES, TABLE3_MODES, policy_loop
from repro.sim.runner import (
    CMPConfig,
    _resolve_allocator_backend,
    _resolve_timeline_backend,
    equal_share,
)


class CapacityInvariantError(RuntimeError):
    """An allocation violated its sums-to-capacity invariant.

    Raised (never ``assert``-ed: the check must survive ``python -O``)
    when a batched cache allocation does not sum to ``total_cache_units``
    per mix, or a dynamic bandwidth allocation does not sum to
    ``total_bandwidth``.
    """


def _check_units_capacity(units: np.ndarray, total_units: int,
                          where: str) -> None:
    sums = np.asarray(units).sum(axis=-1)
    if not (sums == total_units).all():
        raise CapacityInvariantError(
            f"{where}: cache allocation sums {np.unique(sums)} != "
            f"total_cache_units {total_units}")


def _check_bandwidth_capacity(bandwidth: np.ndarray, total_bandwidth: float,
                              where: str) -> None:
    sums = np.asarray(bandwidth).sum(axis=-1)
    if not np.allclose(sums, total_bandwidth, rtol=1e-9, atol=1e-6):
        raise CapacityInvariantError(
            f"{where}: bandwidth allocation sums in "
            f"[{sums.min()}, {sums.max()}] != total_bandwidth "
            f"{total_bandwidth}")


class BatchedCMPPlant:
    """The 16-core CMP interval model over M stacked workload mixes.

    Allocation arrays carry a leading mix axis — ``cache_units`` etc. are
    (M, n) — and every ``run_interval`` is one jitted device call.
    """

    def __init__(self, mixes: Sequence[Sequence[str]],
                 config: Optional[CMPConfig] = None):
        self.mixes: List[List[str]] = [list(m) for m in mixes]
        self.apps: AppArrays = stack_mixes(self.mixes)
        self.config = config or CMPConfig()
        if self.config.backend not in ("numpy", "jax"):
            raise ValueError(f"unknown backend {self.config.backend!r}")
        # config.backend selects the SCALAR plant's model implementation;
        # the batched plant is the JAX path by construction and uses the
        # remaining CMPConfig fields (capacities, llc_extra_cycles) as-is.
        # The allocator follows suit: "auto" keeps allocation on device,
        # and "auto" timelines stack the whole manager set into one device
        # program — unless the allocator was forced onto the host, which
        # only the segment loop can honour (the fused greedy is traced).
        self.allocator_backend = _resolve_allocator_backend(
            self.config, default="jax")
        self.timeline_backend = _resolve_timeline_backend(
            self.config,
            default="stacked" if self.allocator_backend == "jax"
            else "segment")
        self.n_mixes, self.n_clients = np.asarray(self.apps.cpi_base).shape
        self.total_cache_units = self.config.total_cache_units
        self.total_bandwidth = self.config.total_bandwidth

    def evaluate(self, alloc: Allocation) -> memsys.SteadyState:
        return memsys_jax.evaluate(
            self.apps,
            np.asarray(alloc.cache_units, dtype=np.float64),
            alloc.bandwidth,
            alloc.prefetch_on,
            cache_partitioned=alloc.cache_mode != Mode.UNPARTITIONED,
            bandwidth_partitioned=alloc.bandwidth_mode != Mode.UNPARTITIONED,
            total_cache_units=float(self.total_cache_units),
            total_bandwidth_gbps=self.total_bandwidth,
            llc_extra_cycles=self.config.llc_extra_cycles,
            bandwidth_banks=alloc.bandwidth_banks,
        )

    def run_interval(self, alloc: Allocation,
                     duration_ms: float) -> IntervalStats:
        ss = self.evaluate(alloc)
        curves = memsys_jax.utility_curves(
            self.apps, alloc.prefetch_on, ss.ipc,
            self.total_cache_units, duration_ms=1.0)
        ipc = np.asarray(ss.ipc)
        return IntervalStats(
            ipc=ipc,
            queuing_delay_ns=np.asarray(ss.queuing_delay_ns),
            utility_curves=np.asarray(curves),
            instructions=ipc * memsys.FREQ_GHZ * 1e6 * duration_ms,
        )


def baseline_ipc_batched(plant: BatchedCMPPlant) -> np.ndarray:
    """Paper baseline per mix: unpartitioned everything, prefetch off."""
    m, n = plant.n_mixes, plant.n_clients
    units, bw = equal_share(n, plant.total_cache_units, plant.total_bandwidth)
    alloc = Allocation(
        cache_units=np.tile(units, (m, 1)),
        bandwidth=np.tile(bw, (m, 1)),
        prefetch_on=np.zeros((m, n), dtype=bool),
        cache_mode=Mode.UNPARTITIONED,
        bandwidth_mode=Mode.UNPARTITIONED,
    )
    return np.asarray(plant.evaluate(alloc).ipc)


@dataclasses.dataclass
class RowParams:
    """Per-batch-row ``CBPParams`` tunables, broadcast-ready.

    ``schedule`` carries the schedule-shaping fields (common to the whole
    batch); the five non-schedule tunables are scalars without
    ``params_rows`` and per-row arrays with it — min_ways ``(M,)``,
    speedup_threshold / min_bandwidth_allocation / bandwidth_delay_decay
    ``(M, 1)`` (broadcasting against (M, n) state) and atd_decay
    ``(M, 1, 1)`` (against the (M, n, U+1) ATD counters).
    """

    schedule: CBPParams
    min_ways: object
    speedup_threshold: object
    min_bandwidth_allocation: object
    atd_decay: object
    bandwidth_delay_decay: object


def _per_row_params(
    params: CBPParams,
    params_rows: Optional[Sequence[CBPParams]],
    n_rows: int,
) -> RowParams:
    """Resolve the per-row tunables of a (possibly params-batched) sweep.

    With ``params_rows`` the non-schedule tunables become per-row arrays;
    the schedule-shaping fields must agree across rows because every batch
    row executes the same Fig. 8 segment list in lockstep.
    """
    if params_rows is None:
        return RowParams(
            schedule=params,
            min_ways=params.min_ways,
            speedup_threshold=params.speedup_threshold,
            min_bandwidth_allocation=params.min_bandwidth_allocation,
            atd_decay=params.atd_decay,
            bandwidth_delay_decay=params.bandwidth_delay_decay,
        )
    rows = list(params_rows)
    if len(rows) != n_rows:
        raise ValueError(
            f"params_rows has {len(rows)} entries for {n_rows} batch rows")
    sched = {(p.reconfiguration_interval_ms, p.prefetch_sampling_period_ms)
             for p in rows}
    if len(sched) > 1:
        raise ValueError(
            "params_rows must share reconfiguration_interval_ms and "
            "prefetch_sampling_period_ms (the Fig. 8 schedule is common to "
            f"the whole batch); got {sorted(sched)}")
    return RowParams(
        schedule=rows[0],
        min_ways=np.array([p.min_ways for p in rows], dtype=np.int64),
        speedup_threshold=np.array(
            [p.speedup_threshold for p in rows])[:, None],
        min_bandwidth_allocation=np.array(
            [p.min_bandwidth_allocation for p in rows])[:, None],
        atd_decay=np.array([p.atd_decay for p in rows])[:, None, None],
        bandwidth_delay_decay=np.array(
            [p.bandwidth_delay_decay for p in rows])[:, None],
    )


class BatchedCoordinator:
    """One Table-3 manager, coordinated across all mixes in lockstep.

    Mirrors :class:`repro.core.CBPCoordinator` state-for-state with a
    leading mix axis: ATD counters are (M, n, U+1), the shared
    :class:`~repro.core.BandwidthController` accumulates (M, n) delays,
    and the prefetch A/B decision is elementwise.  All mixes share one
    Fig. 8 timeline (it depends only on the manager's prefetch mode and
    the schedule-shaping params), which is what makes lockstep exact.
    Cache allocation is one batched device call per reconfiguration
    boundary (:class:`~repro.core.CacheController` with the plant's
    allocator backend) — never a per-mix host loop.
    """

    def __init__(
        self,
        plant: BatchedCMPPlant,
        params: Optional[CBPParams] = None,
        cache_mode: Mode = Mode.DYNAMIC,
        bandwidth_mode: Mode = Mode.DYNAMIC,
        prefetch_mode: PrefetchMode = PrefetchMode.DYNAMIC,
        params_rows: Optional[Sequence[CBPParams]] = None,
    ):
        self.plant = plant
        self.cache_mode = cache_mode
        self.bandwidth_mode = bandwidth_mode
        self.prefetch_mode = prefetch_mode

        m, n = plant.n_mixes, plant.n_clients
        self.rows = _per_row_params(params or CBPParams(), params_rows, m)
        self.params = self.rows.schedule
        self._min_ways = self.rows.min_ways
        self._thr = self.rows.speedup_threshold
        self._ipc_acc = np.zeros((m, n))
        self._w_acc = 0.0

        units = np.full(n, plant.total_cache_units // n, dtype=np.int64)
        units[: plant.total_cache_units - int(units.sum())] += 1
        self.alloc = Allocation(
            cache_units=np.tile(units, (m, 1)),
            bandwidth=np.full((m, n), plant.total_bandwidth / n),
            prefetch_on=np.full((m, n), prefetch_mode == PrefetchMode.ON,
                                dtype=bool),
            cache_mode=cache_mode,
            bandwidth_mode=bandwidth_mode,
        )

    # ------------------------------------------------------------------ #

    def _run(self, alloc: Allocation, duration_ms: float) -> IntervalStats:
        stats = self.plant.run_interval(alloc, duration_ms)
        self._atd += stats.utility_curves * duration_ms
        self.bw_ctl.observe(stats.queuing_delay_ns * duration_ms)
        self._ipc_acc += stats.ipc * duration_ms
        self._w_acc += duration_ms
        return stats

    def _reconfigure(self) -> None:
        if self.cache_mode == Mode.DYNAMIC:
            self.alloc.cache_units = self.cache_ctl.allocate(
                self._atd, min_units=self._min_ways)
        self._atd *= self.rows.atd_decay
        if self.bandwidth_mode == Mode.DYNAMIC:
            self.alloc.bandwidth = self.bw_ctl.allocate()

    def _with_prefetch(self, value: bool) -> Allocation:
        alloc = self.alloc.copy()
        alloc.prefetch_on = np.full(
            (self.plant.n_mixes, self.plant.n_clients), value, dtype=bool)
        return alloc

    # ------------------------------------------------------------------ #

    def run(self, total_ms: float) -> None:
        """Execute the Fig. 8 timeline over every batch row.

        The default path compiles the whole timeline — every controller
        decision included — into one jitted device program (the K=1 case
        of :func:`repro.sim.timeline_jax.run_timelines`, built from the
        same :func:`_fig8_spec` wiring the stacked sweep uses); the
        "segment" path is the PR 2 host loop of one device call per
        segment, kept for parity testing and debugging.  Both execute the
        identical :func:`~repro.core.fig8_schedule` segment list.
        """
        if self.plant.timeline_backend == "segment":
            self._run_segments(fig8_schedule(
                total_ms, self.params,
                self.prefetch_mode == PrefetchMode.DYNAMIC))
        else:
            # "fused" and "stacked" coincide for a single manager: the
            # per-manager fused program IS the K=1 stacked program.
            self._run_fused(total_ms)
        if self.cache_mode == Mode.DYNAMIC:
            _check_units_capacity(
                self.alloc.cache_units, self.plant.total_cache_units,
                "BatchedCoordinator.run")
        if self.bandwidth_mode == Mode.DYNAMIC:
            _check_bandwidth_capacity(
                self.alloc.bandwidth, self.plant.total_bandwidth,
                "BatchedCoordinator.run")

    def _run_fused(self, total_ms: float) -> None:
        spec = _fig8_spec(self.plant, self.cache_mode, self.bandwidth_mode,
                          self.prefetch_mode, total_ms, self.params)
        res = timeline_jax.run_timelines(
            self.plant.apps, [spec],
            total_units=self.plant.total_cache_units,
            total_bandwidth=self.plant.total_bandwidth,
            llc_extra_cycles=self.plant.config.llc_extra_cycles,
            min_ways=self._min_ways,
            speedup_threshold=self._thr,
            min_bandwidth_allocation=self.rows.min_bandwidth_allocation,
            atd_decay=self.rows.atd_decay,
            bandwidth_delay_decay=self.rows.bandwidth_delay_decay,
        )[0]
        self._ipc_acc = res.ipc_acc
        self._w_acc = res.w_acc
        self.alloc.cache_units = res.cache_units
        self.alloc.bandwidth = res.bandwidth
        self.alloc.prefetch_on = res.prefetch_on

    def _run_segments(self, schedule) -> None:
        # Host-side controller state exists only on this path: the fused
        # program keeps the ATD counters, the delay accumulator and the
        # greedy entirely on device, so building these in __init__ would
        # leave ~1 MB of dead, stale arrays per fused coordinator.
        plant = self.plant
        m, n = plant.n_mixes, plant.n_clients
        self.cache_ctl = CacheController(
            plant.total_cache_units, self.params.min_ways,
            backend=plant.allocator_backend)
        self._atd = np.zeros((m, n, plant.total_cache_units + 1))
        self.bw_ctl = BandwidthController(
            plant.total_bandwidth, self.rows.min_bandwidth_allocation,
            decay=self.rows.bandwidth_delay_decay)
        stats_off: Optional[IntervalStats] = None
        for seg in schedule:
            if seg.kind == "reconfigure":
                self._reconfigure()
            elif seg.kind == "sample_off":
                stats_off = self._run(self._with_prefetch(False),
                                      seg.duration_ms)
            elif seg.kind == "sample_on":
                stats_on = self._run(self._with_prefetch(True),
                                     seg.duration_ms)
                self.alloc.prefetch_on = throttle_decision(
                    stats_on.ipc, stats_off.ipc, self._thr)
            else:
                self._run(self.alloc, seg.duration_ms)

    def mean_ipc(self) -> np.ndarray:
        return self._ipc_acc / max(self._w_acc, 1e-12)


def _run_cppf_batched(plant: BatchedCMPPlant, total_ms: float,
                      params: CBPParams,
                      params_rows: Optional[Sequence[CBPParams]] = None):
    """Vectorized CPpf on the SEGMENT path (mirrors ``managers._run_cppf``).

    Each friendly-mask allocation is ONE batched device call per
    reconfiguration (``CacheController.allocate_masked``).  The fused
    paths never come here: :func:`_manager_spec` is the single source of
    CPpf's fused timeline wiring (``variant="cppf"`` via
    ``timeline_jax.run_timelines``).
    """
    m, n = plant.n_mixes, plant.n_clients
    total_units = plant.total_cache_units
    rows = _per_row_params(params, params_rows, m)
    params = rows.schedule
    equal_units = np.full((m, n), total_units // n, dtype=np.int64)
    bw = np.full((m, n), plant.total_bandwidth / n)

    def make_alloc(units: np.ndarray, pf_on: np.ndarray) -> Allocation:
        return Allocation(
            cache_units=units, bandwidth=bw.copy(), prefetch_on=pf_on,
            cache_mode=Mode.DYNAMIC, bandwidth_mode=Mode.UNPARTITIONED)

    def check(units: np.ndarray) -> None:
        _check_units_capacity(units, total_units, "CPpf")
        _check_bandwidth_capacity(bw, plant.total_bandwidth, "CPpf")

    cache_ctl = CacheController(
        total_units, params.min_ways, backend=plant.allocator_backend)
    off = plant.run_interval(
        make_alloc(equal_units, np.zeros((m, n), dtype=bool)),
        params.prefetch_sampling_period_ms)
    on = plant.run_interval(
        make_alloc(equal_units, np.ones((m, n), dtype=bool)),
        params.prefetch_sampling_period_ms)
    friendly = throttle_decision(on.ipc, off.ipc, rows.speedup_threshold)

    pf_on = np.ones((m, n), dtype=bool)
    units = equal_units.copy()
    atd = np.zeros((m, n, total_units + 1))
    ipc_acc = np.zeros((m, n))
    w_acc = 0.0
    t = 0.0
    while t < total_ms - 1e-9:
        dt = min(params.reconfiguration_interval_ms, total_ms - t)
        stats = plant.run_interval(make_alloc(units, pf_on), dt)
        atd += stats.utility_curves * dt
        ipc_acc += stats.ipc * dt
        w_acc += dt
        t += dt
        curves = atd.copy()
        atd *= rows.atd_decay
        units = cache_ctl.allocate_masked(
            curves, ~friendly, min_units=rows.min_ways)
        check(units)
    return ipc_acc / w_acc, make_alloc(units, pf_on)


def _run_one_manager(
    plant: BatchedCMPPlant,
    name: str,
    total_ms: float,
    params: CBPParams,
    params_rows: Optional[Sequence[CBPParams]] = None,
) -> Tuple[np.ndarray, Allocation]:
    """One manager over every batch row of ``plant`` -> ((M, n) ipc, alloc)."""
    family = policies.get_family(name)
    if family.variant == "cppf":
        return _run_cppf_batched(plant, total_ms, params, params_rows)
    if family.modes is None:
        # Registry policy / banked families: the scalar host golden IS the
        # batched segment path (``policy_loop`` is shape-agnostic), with
        # the per-row tunables threaded through.
        rows = _per_row_params(params, params_rows, plant.n_mixes)
        ipc, alloc = policy_loop(
            plant, family, total_ms, rows.schedule,
            min_ways=rows.min_ways,
            min_bandwidth=rows.min_bandwidth_allocation,
            atd_decay=rows.atd_decay,
            bandwidth_delay_decay=rows.bandwidth_delay_decay)
        where = f"run_sweep[{name}]"
        if _family_modes(family)[0] == Mode.DYNAMIC:
            _check_units_capacity(
                alloc.cache_units, plant.total_cache_units, where)
        _check_bandwidth_capacity(
            alloc.bandwidth, plant.total_bandwidth, where)
        return ipc, alloc
    cache_mode, bw_mode, pf_mode = family.modes
    coord = BatchedCoordinator(
        plant, params=params, cache_mode=cache_mode,
        bandwidth_mode=bw_mode, prefetch_mode=pf_mode,
        params_rows=params_rows)
    coord.run(total_ms)
    return coord.mean_ipc(), coord.alloc


def _family_modes(family: policies.PolicyFamily
                  ) -> Tuple[Mode, Mode, PrefetchMode]:
    """Effective (cache, bandwidth, prefetch) modes of a registry family.

    Classic Table-3 families carry them verbatim; the auction/QoS boundary
    policies manage cache and bandwidth dynamically with prefetch off; the
    banked-bandwidth family keeps cache at the equal split and manages
    bandwidth via Algorithm 1; CPpf partitions cache over unpartitioned
    bandwidth with prefetch enabled.
    """
    if family.modes is not None:
        return family.modes
    if family.variant == "cppf":
        return (Mode.DYNAMIC, Mode.UNPARTITIONED, PrefetchMode.ON)
    if family.cache_policy != policies.CACHE_LOOKAHEAD:
        return (Mode.DYNAMIC, Mode.DYNAMIC, PrefetchMode.OFF)
    return (Mode.EQUAL, Mode.DYNAMIC, PrefetchMode.OFF)


def _fig8_spec(plant: BatchedCMPPlant, cache_mode: Mode, bw_mode: Mode,
               pf_mode: PrefetchMode, total_ms: float, params: CBPParams,
               name: str = "") -> timeline_jax.TimelineSpec:
    """A Fig. 8 coordinator timeline as a TimelineSpec — THE single
    source of the fused fig8 wiring (mode flags, step-0 state,
    schedule), shared by the stacked sweep, the per-manager fused
    reference path and :class:`BatchedCoordinator`.
    """
    m, n = plant.n_mixes, plant.n_clients
    units = np.full(n, plant.total_cache_units // n, dtype=np.int64)
    units[: plant.total_cache_units - int(units.sum())] += 1
    if (cache_mode != Mode.DYNAMIC and bw_mode != Mode.DYNAMIC
            and pf_mode != PrefetchMode.DYNAMIC):
        # Fully static managers have no boundaries to hit and a
        # segmentation-invariant time-weighted mean: one segment spanning
        # the whole timeline evaluates the identical model exactly once
        # instead of once per reconfiguration interval.
        schedule = [ScheduleSegment("run", total_ms)]
    else:
        schedule = fig8_schedule(total_ms, params,
                                 pf_mode == PrefetchMode.DYNAMIC)
    return timeline_jax.TimelineSpec(
        schedule=schedule,
        variant="fig8",
        cache_dynamic=cache_mode == Mode.DYNAMIC,
        bandwidth_dynamic=bw_mode == Mode.DYNAMIC,
        cache_partitioned=cache_mode != Mode.UNPARTITIONED,
        bandwidth_partitioned=bw_mode != Mode.UNPARTITIONED,
        init_units=np.tile(units, (m, 1)),
        init_bandwidth=np.full((m, n), plant.total_bandwidth / n),
        init_prefetch=np.full((m, n), pf_mode == PrefetchMode.ON,
                              dtype=bool),
        name=name)


def _manager_spec(plant: BatchedCMPPlant, name: str, total_ms: float,
                  params: CBPParams) -> timeline_jax.TimelineSpec:
    """One Table-3 manager as a :class:`~repro.sim.timeline_jax.TimelineSpec`.

    Mirrors :func:`_run_cppf_batched`'s segment-path setup exactly — same
    schedules, same step-0 state — so stacking the specs reproduces the
    per-manager runs bit-for-bit.
    """
    m, n = plant.n_mixes, plant.n_clients
    family = policies.get_family(name)
    if family.variant == "cppf":
        return timeline_jax.TimelineSpec(
            schedule=timeline_jax.cppf_schedule(total_ms, params),
            variant="cppf",
            cache_dynamic=True,
            bandwidth_dynamic=False,
            cache_partitioned=True,
            bandwidth_partitioned=False,
            init_units=np.full((m, n), plant.total_cache_units // n,
                               dtype=np.int64),
            init_bandwidth=np.full((m, n), plant.total_bandwidth / n),
            init_prefetch=np.ones((m, n), dtype=bool),
            name=name)
    cache_mode, bw_mode, pf_mode = _family_modes(family)
    spec = _fig8_spec(plant, cache_mode, bw_mode, pf_mode, total_ms,
                      params, name=name)
    if family.modes is None:
        # Registry policy / banked families ride the same fig8 wiring with
        # their traced branch ids and bandwidth regime stamped on.
        spec = dataclasses.replace(
            spec, cache_policy=family.cache_policy,
            bw_policy=family.bw_policy,
            bandwidth_banks=family.bandwidth_banks)
    return spec


def _stage_managers(
    plant: BatchedCMPPlant,
    names: Sequence[str],
    total_ms: float,
    params: CBPParams,
    params_rows: Optional[Sequence[CBPParams]] = None,
) -> Tuple[List[timeline_jax.TimelineSpec], timeline_jax.StagedTimelines]:
    """The manager set's specs and its stacked program, staged on the host
    (:func:`repro.sim.timeline_jax.stage_timelines`), not yet dispatched."""
    rows = _per_row_params(params, params_rows, plant.n_mixes)
    specs = [_manager_spec(plant, name, total_ms, rows.schedule)
             for name in names]
    staged = timeline_jax.stage_timelines(
        plant.apps, specs,
        total_units=plant.total_cache_units,
        total_bandwidth=plant.total_bandwidth,
        llc_extra_cycles=plant.config.llc_extra_cycles,
        min_ways=rows.min_ways,
        speedup_threshold=rows.speedup_threshold,
        min_bandwidth_allocation=rows.min_bandwidth_allocation,
        atd_decay=rows.atd_decay,
        bandwidth_delay_decay=rows.bandwidth_delay_decay,
    )
    return specs, staged


def _run_managers_stacked(
    plant: BatchedCMPPlant,
    names: Sequence[str],
    total_ms: float,
    params: CBPParams,
    params_rows: Optional[Sequence[CBPParams]] = None,
) -> Dict[str, Tuple[np.ndarray, Allocation]]:
    """The whole manager set over every batch row — ONE device program.

    Each manager keeps its own segment table and knob flags; the tables
    stack along the leading manager axis and the (manager, mix) grid
    shards over devices (:func:`repro.sim.timeline_jax.run_timelines`).
    """
    specs, staged = _stage_managers(plant, names, total_ms, params,
                                    params_rows)
    return _collect_managers(plant, specs, staged.dispatch().result())


def _collect_managers(
    plant: BatchedCMPPlant,
    specs: Sequence[timeline_jax.TimelineSpec],
    results: Sequence[timeline_jax.TimelineResult],
) -> Dict[str, Tuple[np.ndarray, Allocation]]:
    """Per manager ``(mean IPC, final allocation)`` from the fetched
    timelines.  Capacity invariants are checked per manager exactly as on
    the per-manager paths."""
    out: Dict[str, Tuple[np.ndarray, Allocation]] = {}
    for spec, res in zip(specs, results):
        if spec.variant == "cppf":
            cache_mode, bw_mode = Mode.DYNAMIC, Mode.UNPARTITIONED
            _check_units_capacity(
                res.cache_units, plant.total_cache_units, "CPpf")
            _check_bandwidth_capacity(
                res.bandwidth, plant.total_bandwidth, "CPpf")
        else:
            cache_mode, bw_mode, _pf = _family_modes(
                policies.get_family(spec.name))
            where = f"run_sweep[{spec.name}]"
            if cache_mode == Mode.DYNAMIC:
                _check_units_capacity(
                    res.cache_units, plant.total_cache_units, where)
            if bw_mode == Mode.DYNAMIC:
                _check_bandwidth_capacity(
                    res.bandwidth, plant.total_bandwidth, where)
        alloc = Allocation(
            cache_units=res.cache_units,
            bandwidth=res.bandwidth,
            prefetch_on=res.prefetch_on,
            cache_mode=cache_mode,
            bandwidth_mode=bw_mode,
            bandwidth_banks=spec.bandwidth_banks,
        )
        out[spec.name] = (res.mean_ipc(), alloc)
    return out


def _run_managers(
    plant: BatchedCMPPlant,
    names: Sequence[str],
    total_ms: float,
    params: CBPParams,
    params_rows: Optional[Sequence[CBPParams]] = None,
) -> Dict[str, Tuple[np.ndarray, Allocation]]:
    """Dispatch a manager set to the plant's timeline backend.

    "stacked" runs every manager in one device program; "fused" runs the
    SAME specs one program per manager (the stacking parity reference —
    bit-identical by construction plus greedy/model batch invariance);
    "segment" loops the PR 2 host path per manager.
    """
    if plant.timeline_backend == "segment":
        return {name: _run_one_manager(plant, name, total_ms, params,
                                       params_rows)
                for name in names}
    if plant.timeline_backend == "stacked" and names:
        return _run_managers_stacked(
            plant, names, total_ms, params, params_rows)
    out: Dict[str, Tuple[np.ndarray, Allocation]] = {}
    for name in names:
        out.update(_run_managers_stacked(
            plant, [name], total_ms, params, params_rows))
    return out


@dataclasses.dataclass
class SweepResult:
    """Per-(manager, mix, app) outcome of one sweep.

    Without ``param_grid`` the arrays are (M, n); with it they gain a
    leading params axis, (P, M, n), and the metric helpers broadcast
    accordingly (``weighted_speedup`` -> (P, M), ``geomean_speedup`` ->
    (P,)).  The baseline is parameter-independent and stays (M, n).
    """

    manager_names: List[str]
    mixes: List[List[str]]
    ipc: Dict[str, np.ndarray]            # name -> (M, n) | (P, M, n)
    final_alloc: Dict[str, Allocation]    # name -> batched allocation
    baseline_ipc: np.ndarray              # (M, n)
    param_grid: Optional[List[CBPParams]] = None

    @property
    def n_mixes(self) -> int:
        return len(self.mixes)

    def weighted_speedup(self, name: str) -> np.ndarray:
        """Paper §4.3 weighted speedup per mix, shape (M,) (or (P, M))."""
        return np.mean(self.ipc[name] / self.baseline_ipc, axis=-1)

    def antt(self, name: str) -> np.ndarray:
        """Paper §4.3 avg normalized turnaround time per mix, (M,)/(P, M)."""
        return np.mean(self.baseline_ipc / self.ipc[name], axis=-1)

    def geomean_speedup(self, name: str):
        """Geomean over mixes: float, or (P,) with a ``param_grid``."""
        g = np.exp(np.mean(np.log(self.weighted_speedup(name)), axis=-1))
        return float(g) if np.ndim(g) == 0 else g

    def summary(self) -> Dict[str, object]:
        """Geomean weighted speedup per manager over all mixes."""
        out: Dict[str, object] = {}
        for name in self.manager_names:
            g = self.geomean_speedup(name)
            out[name] = (round(g, 4) if np.ndim(g) == 0
                         else [round(float(x), 4) for x in np.asarray(g)])
        return out


def _run_sweep_one(
    mixes: Sequence[Sequence[str]],
    managers: Optional[Sequence[str]],
    total_ms: float,
    params: CBPParams,
    config: Optional[CMPConfig],
) -> SweepResult:
    """:func:`run_sweep` with one ``CBPParams``, in host spans
    (:func:`repro.core.dispatch.span`) that a profiler trace shows beside
    the device's ops: ``cbp.sweep.prepare`` (plant, specs, segment tables,
    the stacked grid), then the stacked program's dispatch, then
    ``cbp.sweep.collect`` (the per-spec slices, the fetch, the capacity
    checks and the mean IPC) and ``cbp.sweep.baseline`` (the baseline
    program and its fetch).  Timeline backends other than "stacked" run
    their managers between the prepare and baseline spans, in no span."""
    with span("cbp.sweep.prepare"):
        plant = BatchedCMPPlant(mixes, config)
        names = list(MANAGER_NAMES) if managers is None else list(managers)
        policies.validate_manager_names(names)
        stacked = plant.timeline_backend == "stacked" and bool(names)
        if stacked:
            specs, staged = _stage_managers(plant, names, total_ms, params)
    if stacked:
        pending = staged.dispatch()
        with span("cbp.sweep.collect"):
            out = _collect_managers(plant, specs, pending.result())
    else:
        out = _run_managers(plant, names, total_ms, params)
    with span("cbp.sweep.baseline"):
        baseline = baseline_ipc_batched(plant)
    return SweepResult(
        manager_names=names,
        mixes=plant.mixes,
        ipc={name: mipc for name, (mipc, _) in out.items()},
        final_alloc={name: alloc for name, (_, alloc) in out.items()},
        baseline_ipc=baseline,
    )


def run_sweep(
    mixes: Sequence[Sequence[str]],
    managers: Optional[Sequence[str]] = None,
    total_ms: float = 100.0,
    params: Optional[CBPParams] = None,
    config: Optional[CMPConfig] = None,
    param_grid: Optional[Sequence[CBPParams]] = None,
) -> SweepResult:
    """Evaluate Table-3 managers over many mixes in batched device calls.

    Args:
      mixes: equal-size workload mixes (lists of app names) — e.g.
        ``list(WORKLOADS.values())`` or :func:`repro.sim.random_mixes`.
      managers: manager names (default: all ``MANAGER_NAMES``).
      total_ms / params / config: as in ``managers.run_manager``.
      param_grid: optional sequence of ``CBPParams`` — adds a leading P
        axis to the results (Fig. 12 design-space exploration as one
        sweep).  Params sharing a Fig. 8 schedule are stacked into a
        single device-resident batch of P_g x M rows; schedule-distinct
        params run as separate batches of the same sweep.  Mutually
        exclusive with ``params``.
    """
    if param_grid is None:
        return _run_sweep_one(mixes, managers, total_ms,
                              params or CBPParams(), config)
    plant = BatchedCMPPlant(mixes, config)
    names = list(MANAGER_NAMES) if managers is None else list(managers)
    policies.validate_manager_names(names)   # UnknownManagerError on a typo
    if params is not None:
        raise ValueError("pass either params or param_grid, not both")
    grid = list(param_grid)
    if not grid:
        raise ValueError("param_grid must be non-empty")
    P, M, n = len(grid), plant.n_mixes, plant.n_clients
    ipc = {name: np.empty((P, M, n)) for name in names}
    units = {name: np.empty((P, M, n), dtype=np.int64) for name in names}
    bws = {name: np.empty((P, M, n)) for name in names}
    pfs = {name: np.empty((P, M, n), dtype=bool) for name in names}
    modes: Dict[str, Tuple[Mode, Mode]] = {}

    def _params_static(name: str) -> bool:
        """True when no CBPParams field can change the manager's result:
        nothing dynamic means no reconfiguration, no A/B sampling, and a
        time-weighted mean that is segmentation-invariant."""
        family = policies.get_family(name)
        if family.modes is None:
            # CPpf and the registry policy / banked families all manage
            # at least one resource dynamically.
            return False
        cm, bm, pm = family.modes
        return (cm != Mode.DYNAMIC and bm != Mode.DYNAMIC
                and pm != PrefetchMode.DYNAMIC)

    static_names = [name for name in names if _params_static(name)]
    for name, (mipc, alloc) in _run_managers(
            plant, static_names, total_ms, grid[0]).items():
        ipc[name][:] = np.asarray(mipc)[None]
        units[name][:] = np.asarray(alloc.cache_units)[None]
        bws[name][:] = np.asarray(alloc.bandwidth)[None]
        pfs[name][:] = np.asarray(alloc.prefetch_on)[None]
        modes[name] = (alloc.cache_mode, alloc.bandwidth_mode)
    grid_names = [name for name in names if name not in static_names]

    groups: Dict[Tuple[float, float], List[int]] = {}
    for pi, p in enumerate(grid):
        key = (p.reconfiguration_interval_ms, p.prefetch_sampling_period_ms)
        groups.setdefault(key, []).append(pi)

    for idxs in (groups.values() if grid_names else ()):
        tiled = [mix for _ in idxs for mix in mixes]
        gplant = BatchedCMPPlant(tiled, config)
        rows = [grid[pi] for pi in idxs for _ in range(M)]
        G = len(idxs)
        for name, (mipc, alloc) in _run_managers(
                gplant, grid_names, total_ms, rows[0],
                params_rows=rows).items():
            ipc[name][idxs] = np.asarray(mipc).reshape(G, M, n)
            units[name][idxs] = np.asarray(
                alloc.cache_units).reshape(G, M, n)
            bws[name][idxs] = np.asarray(alloc.bandwidth).reshape(G, M, n)
            pfs[name][idxs] = np.asarray(
                alloc.prefetch_on).reshape(G, M, n)
            modes[name] = (alloc.cache_mode, alloc.bandwidth_mode)

    final = {
        name: Allocation(
            cache_units=units[name], bandwidth=bws[name],
            prefetch_on=pfs[name], cache_mode=modes[name][0],
            bandwidth_mode=modes[name][1])
        for name in names
    }
    return SweepResult(
        manager_names=names,
        mixes=plant.mixes,
        ipc=ipc,
        final_alloc=final,
        baseline_ipc=baseline_ipc_batched(plant),
        param_grid=grid,
    )
