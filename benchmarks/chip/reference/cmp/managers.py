"""The resource-manager configurations of paper Table 3 plus the
registry's related-work families.

Every manager runs on the same :class:`~repro.sim.runner.CMPPlant`; the
subset managers reuse the CBP coordinator with the unmanaged resources
pinned, exactly mirroring how the paper builds its comparison points.
CPpf [Xiao et al. '19] is implemented per paper §4.4: prefetch-friendly
applications receive the minimum partition; UCP partitions the remaining
capacity among the rest; prefetching enabled; bandwidth unpartitioned.
The auction / QoS / banked-bandwidth families declared in
:mod:`repro.sim.policies` run through :func:`policy_loop`, the shared
numpy host golden the batched sweep's segment path reuses verbatim.

``MANAGER_NAMES`` and ``TABLE3_MODES`` are *derived* from the policy
registry (``tests/test_sim_managers.py`` pins registry completeness:
every family has a host golden, a traced branch and a static-grid
vocabulary), and this module attaches each family's ``host_golden`` at
import time so the registry itself stays free of plant imports.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

from .cache_controller import CacheController
from .coordinator import CBPCoordinator, fig8_schedule
from .prefetch_controller import throttle_decision
from .types import Allocation, CBPParams, Mode, PrefetchMode
from .atd import SampledATD
from .bandwidth_controller import allocate_bandwidth
from . import policies
from .policies import UnknownManagerError  # re-export
from .runner import CMPPlant

MANAGER_NAMES = policies.manager_names()

# (cache_mode, bandwidth_mode, prefetch_mode) per Table 3 — the classic
# mode-combination subset of the registry.
TABLE3_MODES = policies.table3_modes()


@dataclasses.dataclass
class ManagerResult:
    name: str
    ipc: np.ndarray                 # time-weighted mean per-app IPC
    final_alloc: Optional[Allocation] = None


def run_manager(
    name: str,
    plant: CMPPlant,
    total_ms: float = 100.0,
    params: Optional[CBPParams] = None,
) -> ManagerResult:
    params = params or CBPParams()
    family = policies.get_family(name)   # raises UnknownManagerError
    if family.variant == "cppf":
        return _run_cppf(plant, total_ms, params)
    if family.modes is None:
        ipc, alloc = policy_loop(plant, family, total_ms, params)
        return ManagerResult(name=name, ipc=ipc, final_alloc=alloc)
    cache_mode, bw_mode, pf_mode = family.modes
    coord = CBPCoordinator(
        plant, params=params,
        cache_mode=cache_mode, bandwidth_mode=bw_mode, prefetch_mode=pf_mode)
    coord.run(total_ms)
    return ManagerResult(name=name, ipc=coord.mean_ipc(),
                         final_alloc=coord.alloc)


def policy_loop(
    plant,
    family: policies.PolicyFamily,
    total_ms: float,
    params: CBPParams,
    *,
    min_ways=None,
    min_bandwidth=None,
    atd_decay=None,
    bandwidth_delay_decay=None,
):
    """Numpy host golden for the registry's policy / banked families.

    Mirrors the stacked scan's boundary semantics op-for-op
    (:mod:`repro.sim.timeline_jax`): per executed interval the ATD
    counters accumulate ``curves * dt`` and the delay EMA advances by
    ``decay * acc + q_ns * dt`` (which starts as a plain copy, matching
    :class:`~repro.core.BandwidthController`'s first observe); the QoS
    slowdown reference is the first executed interval's IPC (the
    equal-share initial state) over the most recent one; at each Fig. 8
    boundary the family's allocators fire and THEN the ATD decays.

    Shape-agnostic over a leading batch axis: ``plant`` may be the scalar
    :class:`~repro.sim.runner.CMPPlant` (state ``(n,)``) or the sweep's
    ``BatchedCMPPlant`` (state ``(M, n)``), with the per-row tunable
    overrides the batched segment path threads through — which is how the
    sweep's segment backend and the scalar golden stay ONE function.

    Returns ``(mean_ipc, final Allocation)``.
    """
    n = plant.n_clients
    total_units = plant.total_cache_units
    total_bw = plant.total_bandwidth
    m = getattr(plant, "n_mixes", None)
    lead = () if m is None else (m,)

    if min_ways is None:
        min_ways = params.min_ways
    if min_bandwidth is None:
        min_bandwidth = params.min_bandwidth_allocation
    if atd_decay is None:
        atd_decay = params.atd_decay
    if bandwidth_delay_decay is None:
        bandwidth_delay_decay = params.bandwidth_delay_decay

    # auction/qos allocate both resources from their boundary branch;
    # "bank bw" keeps cache at the equal split and runs Algorithm 1
    # under the banked-token memory regime.
    is_policy = family.cache_policy != policies.CACHE_LOOKAHEAD
    cache_mode = Mode.DYNAMIC if is_policy else Mode.EQUAL

    units = np.full(n, total_units // n, dtype=np.int64)
    units[: total_units - int(units.sum())] += 1
    units = np.broadcast_to(units, lead + (n,)).copy()
    bw = np.full(lead + (n,), total_bw / n)
    pf = np.zeros(lead + (n,), dtype=bool)

    def make_alloc(units, bw):
        return Allocation(
            cache_units=units, bandwidth=bw, prefetch_on=pf,
            cache_mode=cache_mode, bandwidth_mode=Mode.DYNAMIC,
            bandwidth_banks=family.bandwidth_banks)

    atd = np.zeros(lead + (n, total_units + 1))
    bw_acc = np.zeros(lead + (n,))
    ref_ipc = np.zeros(lead + (n,))
    prev_ipc = np.zeros(lead + (n,))
    ipc_acc = np.zeros(lead + (n,))
    w_acc = 0.0
    for seg in fig8_schedule(total_ms, params, False):
        if seg.kind == "reconfigure":
            curves = atd.copy()
            if family.cache_policy == policies.CACHE_AUCTION:
                units, bw = policies.auction_allocate(
                    curves, bw_acc, min_ways=min_ways,
                    total_units=total_units, min_bandwidth=min_bandwidth,
                    total_bandwidth=total_bw)
            elif family.cache_policy == policies.CACHE_QOS:
                slow = np.where(
                    prev_ipc > 0,
                    ref_ipc / np.where(prev_ipc > 0, prev_ipc, 1.0), 1.0)
                units, bw = policies.qos_allocate(
                    curves, bw_acc, slow, min_ways=min_ways,
                    total_units=total_units, min_bandwidth=min_bandwidth,
                    total_bandwidth=total_bw)
            else:
                bw = allocate_bandwidth(bw_acc, total_bw, min_bandwidth)
            atd *= atd_decay
        else:
            dt = seg.duration_ms
            stats = plant.run_interval(make_alloc(units, bw), dt)
            atd += stats.utility_curves * dt
            bw_acc = bandwidth_delay_decay * bw_acc \
                + stats.queuing_delay_ns * dt
            ref_ipc = np.where(ref_ipc == 0.0, stats.ipc, ref_ipc)
            prev_ipc = stats.ipc
            ipc_acc += stats.ipc * dt
            w_acc += dt
    return ipc_acc / max(w_acc, 1e-12), make_alloc(units, bw)


def _run_cppf(plant: CMPPlant, total_ms: float,
              params: CBPParams) -> ManagerResult:
    """CPpf: prefetch-aware LLC partitioning (paper §4.4 implementation).

    Prefetch-friendly apps -> min allocation (prefetching offsets the small
    partition); UCP over the remaining capacity for the others; bandwidth
    unpartitioned; prefetching enabled.
    """
    n = plant.n_clients
    total_units = plant.total_cache_units
    atd = SampledATD(n, total_units)
    cache_ctl = CacheController(
        total_units, params.min_ways,
        backend=getattr(plant, "allocator_backend", "numpy"))

    equal_units = np.full(n, total_units // n, dtype=np.int64)
    bw = np.full(n, plant.total_bandwidth / n)

    def make_alloc(units: np.ndarray, pf_on: np.ndarray) -> Allocation:
        return Allocation(
            cache_units=units, bandwidth=bw.copy(), prefetch_on=pf_on,
            cache_mode=Mode.DYNAMIC, bandwidth_mode=Mode.UNPARTITIONED)

    # Friendliness probe (A/B sample at equal partitioning).
    off = plant.run_interval(
        make_alloc(equal_units, np.zeros(n, dtype=bool)),
        params.prefetch_sampling_period_ms)
    on = plant.run_interval(
        make_alloc(equal_units, np.ones(n, dtype=bool)),
        params.prefetch_sampling_period_ms)
    friendly = throttle_decision(on.ipc, off.ipc, params.speedup_threshold)

    pf_on = np.ones(n, dtype=bool)  # Table 3: prefetch setting "enabled"
    units = equal_units.copy()
    t = 0.0
    ipc_acc = np.zeros(n)
    w_acc = 0.0
    while t < total_ms - 1e-9:
        dt = min(params.reconfiguration_interval_ms, total_ms - t)
        stats = plant.run_interval(make_alloc(units, pf_on), dt)
        atd.record(stats.utility_curves * dt)
        ipc_acc += stats.ipc * dt
        w_acc += dt
        t += dt
        # Reallocate: friendly pinned at min; UCP for the rest over the
        # remaining capacity.
        curves = atd.utility_curves()
        atd.halve(params.atd_decay)
        units = cache_ctl.allocate_masked(curves, ~friendly)
    return ManagerResult(
        name="CPpf", ipc=ipc_acc / w_acc,
        final_alloc=make_alloc(units, pf_on))


def run_all_managers(
    workload: Sequence[str],
    total_ms: float = 100.0,
    names: Optional[List[str]] = None,
    params: Optional[CBPParams] = None,
    config=None,
) -> Dict[str, ManagerResult]:
    plant = CMPPlant(workload, config)
    return {
        name: run_manager(name, plant, total_ms, params)
        for name in (names or MANAGER_NAMES)
    }


# Attach every family's scalar host golden to the registry (the registry
# module itself never imports the plant stack, so this is the one place
# the binding can happen without an import cycle).
for _name in policies.manager_names():
    _fam = policies.get_family(_name)
    if _fam.host_golden is None:
        _fam.host_golden = functools.partial(run_manager, _name)
del _name, _fam
