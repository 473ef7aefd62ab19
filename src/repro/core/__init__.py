"""CBP: coordinated cache partitioning, bandwidth partitioning and prefetch
throttling (Holtryd et al., 2021) — the paper's primary contribution.

The three local controllers (paper §3.2) and the coordination mechanism
(paper §3.3) are domain-agnostic; they are bound to the CMP interval model in
``repro.sim`` (faithful reproduction) and to TPU memory-system knobs in
``repro.runtime`` / ``repro.serving`` / ``repro.kernels`` (hardware
adaptation — see DESIGN.md §2).
"""
from repro.core.atd import SampledATD, StackDistanceMonitor
from repro.core.bandwidth_controller import (
    BandwidthController,
    allocate_bandwidth,
    allocate_bandwidth_jax,
    check_bandwidth_floor,
)
from repro.core.cache_controller import (
    CacheController,
    allocator_calls,
    cppf_allocate,
    lookahead_allocate,
    reset_allocator_calls,
)
from repro.core.coordinator import (
    CBPCoordinator,
    Plant,
    ScheduleSegment,
    fig8_schedule,
)
from repro.core.dispatch import (
    device_dispatches,
    greedy_trips,
    record_dispatch,
    reset_device_dispatches,
)
from repro.core.prefetch_controller import (
    PrefetchController,
    throttle_decision,
    throttle_decision_jax,
)
from repro.core.types import (
    Allocation,
    CBPParams,
    IntervalStats,
    Mode,
    PrefetchMode,
    ScheduleConfigError,
)

__all__ = [
    "SampledATD",
    "StackDistanceMonitor",
    "BandwidthController",
    "allocate_bandwidth",
    "allocate_bandwidth_jax",
    "check_bandwidth_floor",
    "CacheController",
    "allocator_calls",
    "cppf_allocate",
    "lookahead_allocate",
    "reset_allocator_calls",
    "CBPCoordinator",
    "Plant",
    "ScheduleSegment",
    "fig8_schedule",
    "device_dispatches",
    "greedy_trips",
    "record_dispatch",
    "reset_device_dispatches",
    "PrefetchController",
    "throttle_decision",
    "throttle_decision_jax",
    "Allocation",
    "CBPParams",
    "IntervalStats",
    "Mode",
    "PrefetchMode",
    "ScheduleConfigError",
]
