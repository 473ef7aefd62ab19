"""The CMP plant: binds the interval model to the CBP coordinator.

:class:`CMPPlant` implements the :class:`repro.core.coordinator.Plant`
protocol — ``run_interval`` evaluates the steady-state model under an
allocation and reports IPC, queuing delays and ATD utility curves.  This is
the substrate on which all Table-3 resource managers execute.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from .types import Allocation, IntervalStats, Mode
from . import apps as apps_mod
from . import memsys
from .apps import AppArrays, stack


@dataclasses.dataclass
class CMPConfig:
    total_cache_units: int = apps_mod.TOTAL_UNITS_8MB
    total_bandwidth: float = apps_mod.TOTAL_BW_GBPS
    llc_extra_cycles: float = 0.0   # added LLC hit latency (bigger tiles)


class CMPPlant:
    """16-core tiled CMP interval model (paper Table 1) as a CBP plant,
    on the numpy interval model."""

    def __init__(self, workload: Sequence[str],
                 config: Optional[CMPConfig] = None,
                 apps: Optional[AppArrays] = None):
        self.apps: AppArrays = apps if apps is not None else stack(
            list(workload))
        self.config = config or CMPConfig()
        self.allocator_backend = "numpy"
        self.n_clients = self.apps.n
        self.total_cache_units = self.config.total_cache_units
        self.total_bandwidth = self.config.total_bandwidth

    def evaluate(self, alloc: Allocation) -> memsys.SteadyState:
        return memsys.evaluate(
            self.apps,
            alloc.cache_units.astype(np.float64),
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
        curves = np.asarray(memsys.utility_curves(
            self.apps, alloc.prefetch_on, ss.ipc,
            self.total_cache_units, duration_ms=1.0))
        instr = ss.ipc * memsys.FREQ_GHZ * 1e6 * duration_ms
        return IntervalStats(
            ipc=ss.ipc,
            queuing_delay_ns=ss.queuing_delay_ns,
            utility_curves=curves,
            instructions=instr,
        )


def equal_share(n: int, total_units, total_bandwidth):
    """Equal-share per-app allocation — the ONE baseline construction.

    Every baseline in the repo splits capacity this way: ``total_units
    // n`` cache units each (integer floor) and exactly
    ``total_bandwidth / n`` GB/s each.  Shared by the scalar baseline
    (:func:`baseline_ipc`), the batched sweep baseline
    (:func:`repro.sim.sweep.baseline_ipc_batched`) and the Fig. 5 static
    search (:mod:`repro.sim.static_search`,
    ``benchmarks.paper_figs._exhaustive_best``) so the protocols cannot
    drift apart; only the partitioning mode differs per protocol.
    """
    units = np.full(n, int(total_units) // n, dtype=np.int64)
    bw = np.full(n, float(total_bandwidth) / n, dtype=np.float64)
    return units, bw


def baseline_ipc(workload: Sequence[str],
                 config: Optional[CMPConfig] = None) -> np.ndarray:
    """Paper baseline: unpartitioned cache + bandwidth, prefetch disabled."""
    plant = CMPPlant(workload, config)
    n = plant.n_clients
    units, bw = equal_share(n, plant.total_cache_units, plant.total_bandwidth)
    alloc = Allocation(
        cache_units=units,
        bandwidth=bw,
        prefetch_on=np.zeros(n, dtype=bool),
        cache_mode=Mode.UNPARTITIONED,
        bandwidth_mode=Mode.UNPARTITIONED,
    )
    return plant.evaluate(alloc).ipc


def weighted_speedup(ipc_rm: np.ndarray, ipc_base: np.ndarray) -> float:
    """Paper §4.3: (1/N) * sum(IPC_RM / IPC_baseline)."""
    return float(np.mean(ipc_rm / ipc_base))


def antt(ipc_rm: np.ndarray, ipc_base: np.ndarray) -> float:
    """Paper §4.3: average normalized turnaround time (lower is better)."""
    return float(np.mean(ipc_base / ipc_rm))
