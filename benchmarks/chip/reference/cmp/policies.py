"""The manager-family policy registry (ROADMAP item 4).

Every resource-manager family the repo can evaluate is declared HERE, once,
as a :class:`PolicyFamily`.  A family registers three things:

* a **numpy host golden** — the scalar reference loop
  (:mod:`repro.sim.managers` attaches it at import time, so the registry
  never imports the plant stack and stays cycle-free);
* a **traced allocator branch** — the ``cache_policy`` / ``bw_policy`` ids
  select the family's boundary allocators inside the stacked Fig. 8 scan
  (:mod:`repro.sim.timeline_jax` builds its ``lax.switch`` branch tables
  from :data:`CACHE_POLICY_NAMES` / :data:`BW_POLICY_NAMES`, so an id
  outside those tables cannot trace), and ``bandwidth_banks`` selects the
  interval model's bandwidth regime
  (:mod:`repro.sim.memsys` / :mod:`repro.sim.memsys_jax`);
* a **static-grid vocabulary** — which knobs the family's Fig. 5 static
  search may move (:func:`repro.sim.static_search.registry_families`
  turns it into a ``FamilySpec``).

``MANAGER_NAMES`` and ``TABLE3_MODES`` are *derived* from the registry
(:func:`manager_names` / :func:`table3_modes`) instead of hand-pinned
lists, so adding family #15 is: declare it here, attach its host golden,
give its traced branch an id — every sweep/search/stream entry point picks
it up (``tests/test_sim_managers.py`` pins registry completeness).

The three non-Table-3 families added with the registry:

* ``"auction"`` — CARMA-style market allocation (arxiv 1710.00073): each
  client spends a unit budget across cache and bandwidth in proportion to
  its normalized desire for each (ATD marginal hits resp. accumulated
  queuing delay); allocations are pro-rata in spend over the floors.
* ``"qos"`` — QoS-constrained throughput maximization (Nejat et al.,
  arxiv 1911.05114): demand-proportional shares, boosted for clients whose
  slowdown against their first-interval (equal-share) reference exceeds
  the bound — the traced form carries that slowdown signal in the scan.
* ``"bank bw"`` — per-bank bandwidth tokens (arxiv 2410.14003): Algorithm-1
  bandwidth partitioning evaluated under the banked-token memory model
  (``bandwidth_banks > 1``), of which the flat partitioned mode is the
  1-bank special case.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .types import Mode, PrefetchMode

# --------------------------------------------------------------------- #
# traced branch tables
# --------------------------------------------------------------------- #

#: Cache boundary allocator branch ids (``lax.switch`` order inside the
#: stacked scan — :mod:`repro.sim.timeline_jax`).
CACHE_LOOKAHEAD, CACHE_AUCTION, CACHE_QOS = 0, 1, 2
CACHE_POLICY_NAMES: Tuple[str, ...] = ("lookahead", "auction", "qos")

#: Bandwidth boundary allocator branch ids.
BW_ALG1, BW_AUCTION, BW_QOS = 0, 1, 2
BW_POLICY_NAMES: Tuple[str, ...] = ("alg1", "auction", "qos")

#: Per-client auction budget (CARMA's per-agent endowment; only spend
#: *proportions* matter, the scale cancels in the pro-rata shares).
AUCTION_BUDGET = 1.0
AUCTION_EPS = 1e-12

#: QoS family tunables: clients whose slowdown against their first-interval
#: (equal-share) reference exceeds the bound get their demand weight
#: boosted by ``1 + gain * violation``.
QOS_SLOWDOWN_BOUND = 1.05
QOS_VIOLATION_GAIN = 8.0


class UnknownManagerError(ValueError):
    """An unregistered manager-family name reached a sweep entry point.

    Raised by :func:`get_family` (and therefore ``run_manager`` /
    ``run_sweep`` / ``stream_sweep``) naming the bad key and listing the
    registered families — instead of the bare ``KeyError`` a missing dict
    entry used to die with.  Consistent with
    :class:`~repro.sim.static_search.InfeasibleGridError` /
    :class:`~repro.core.types.ScheduleConfigError`: a typed, actionable
    configuration error.
    """

    def __init__(self, name: str, extra: Tuple[str, ...] = ()):
        valid = list(extra) + manager_names()
        super().__init__(
            f"unknown manager {name!r}; registered families: {valid}")
        self.name = name
        self.valid = valid


@dataclasses.dataclass
class PolicyFamily:
    """One manager family's registry entry.

    ``modes`` is the Table-3 ``(cache, bandwidth, prefetch)`` mode triple
    for the classic mode-combination families (``None`` for families with
    their own wiring — CPpf's variant timeline, the auction/QoS boundary
    policies, the banked-bandwidth model regime).  ``host_golden`` is
    attached by :mod:`repro.sim.managers` at import time; it maps
    ``(plant, total_ms, params) -> ManagerResult``.  ``static_grid`` is
    the Fig. 5 vocabulary as plain kwargs (``manage_cache`` /
    ``manage_bw`` / ``manage_pf`` / ``pf_all_on`` / ``bandwidth_banks``)
    so the registry never imports the search stack.
    """

    name: str
    modes: Optional[Tuple[Mode, Mode, PrefetchMode]] = None
    variant: str = "fig8"              # timeline variant ("fig8" | "cppf")
    cache_policy: int = CACHE_LOOKAHEAD
    bw_policy: int = BW_ALG1
    bandwidth_banks: int = 1
    static_grid: Optional[Dict[str, object]] = None
    host_golden: Optional[Callable] = None

    def __post_init__(self):
        if not 0 <= self.cache_policy < len(CACHE_POLICY_NAMES):
            raise ValueError(
                f"{self.name!r}: cache_policy {self.cache_policy} has no "
                f"traced branch (table: {CACHE_POLICY_NAMES})")
        if not 0 <= self.bw_policy < len(BW_POLICY_NAMES):
            raise ValueError(
                f"{self.name!r}: bw_policy {self.bw_policy} has no traced "
                f"branch (table: {BW_POLICY_NAMES})")
        if self.bandwidth_banks < 1:
            raise ValueError(
                f"{self.name!r}: bandwidth_banks must be >= 1, got "
                f"{self.bandwidth_banks}")


REGISTRY: Dict[str, PolicyFamily] = {}


def register(family: PolicyFamily) -> PolicyFamily:
    if family.name in REGISTRY:
        raise ValueError(f"family {family.name!r} already registered")
    REGISTRY[family.name] = family
    return family


def manager_names() -> List[str]:
    """Registry insertion order — THE manager-name list of every sweep."""
    return list(REGISTRY)


def table3_modes() -> Dict[str, Tuple[Mode, Mode, PrefetchMode]]:
    """The classic mode-combination families (``modes`` is not ``None``)."""
    return {name: fam.modes for name, fam in REGISTRY.items()
            if fam.modes is not None}


def get_family(name: str) -> PolicyFamily:
    try:
        return REGISTRY[name]
    except KeyError:
        raise UnknownManagerError(name) from None


def validate_manager_names(names, extra: Tuple[str, ...] = ()) -> None:
    """Raise :class:`UnknownManagerError` on the first unregistered name.

    ``extra`` admits caller-specific pseudo-families (the streaming sweep
    accepts them on top of the registry).
    """
    for name in names:
        if name not in REGISTRY and name not in extra:
            raise UnknownManagerError(name, tuple(extra))


# --------------------------------------------------------------------- #
# numpy host allocators (golden references; jax mirrors below)
# --------------------------------------------------------------------- #

def _per_client(value, like: np.ndarray) -> np.ndarray:
    """Broadcast a scalar / per-batch-row tunable against (..., n) state."""
    arr = np.asarray(value)
    arr = arr.reshape(arr.shape + (1,) * (like.ndim - arr.ndim))
    return np.broadcast_to(arr, like.shape)


def _shares(weights: np.ndarray, n: int) -> np.ndarray:
    """Pro-rata shares with the Algorithm-1 zero-total fallback (1/n)."""
    total = weights.sum(axis=-1, keepdims=True)
    return np.where(total > 0,
                    weights / np.where(total > 0, total, 1.0),
                    1.0 / n)


def largest_remainder_round(target: np.ndarray,
                            total_units: int) -> np.ndarray:
    """Round per-client float targets to ints summing exactly to capacity.

    Floor everything, then grant the leftover units to the largest
    fractional parts (stable: equal fractions break toward the lowest
    client index).  ``target`` must sum to ``total_units`` per batch row
    up to float noise and sit at or above any integer floor the caller
    already folded in — both hold for pro-rata-over-floors targets.
    """
    base = np.floor(target)
    frac = target - base
    deficit = np.rint(total_units - base.sum(axis=-1)).astype(np.int64)
    order = np.argsort(-frac, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    return (base + (rank < deficit[..., None])).astype(np.int64)


def _cache_desire(curves: np.ndarray, min_ways: np.ndarray) -> np.ndarray:
    """Marginal ATD utility: hits gained going from the floor to the whole
    cache — a client whose curve is flat past its floor desires nothing."""
    top = curves[..., -1]
    at_min = np.take_along_axis(
        curves, min_ways[..., None].astype(np.int64), axis=-1)[..., 0]
    return np.maximum(top - at_min, 0.0)


def auction_allocate(
    curves: np.ndarray,
    bw_delay: np.ndarray,
    *,
    min_ways,
    total_units: int,
    min_bandwidth,
    total_bandwidth: float,
) -> Tuple[np.ndarray, np.ndarray]:
    """CARMA-style auction over cache ways and bandwidth (numpy golden).

    Each client splits a unit budget between the two resources in
    proportion to its *normalized* desires (mean-normalized so the two
    signals' units cancel): marginal ATD hits for cache, accumulated
    queuing delay for bandwidth.  Resources are then allocated pro-rata in
    spend over the per-client floors; cache spends round to integers by
    largest remainder.

    Args:
      curves: (..., n, U+1) accumulated ATD utility curves.
      bw_delay: (..., n) accumulated queuing delays.
      min_ways: scalar or per-batch-row floor (ways).
      min_bandwidth: scalar or (..., 1) per-row floor (GB/s).

    Returns:
      ``(cache_units, bandwidth)`` — (..., n) int64 summing to
      ``total_units`` and (..., n) float summing to ``total_bandwidth``.
    """
    n = bw_delay.shape[-1]
    mw = _per_client(min_ways, bw_delay).astype(np.float64)
    cd = _cache_desire(curves, mw)
    cd_n = cd / np.maximum(cd.mean(axis=-1, keepdims=True), AUCTION_EPS)
    bd_n = bw_delay / np.maximum(
        bw_delay.mean(axis=-1, keepdims=True), AUCTION_EPS)
    frac_cache = cd_n / (cd_n + bd_n + AUCTION_EPS)
    spend_cache = AUCTION_BUDGET * frac_cache
    spend_bw = AUCTION_BUDGET - spend_cache

    target = mw + _shares(spend_cache, n) * (
        total_units - mw.sum(axis=-1, keepdims=True))
    units = largest_remainder_round(target, total_units)
    min_bw = np.asarray(min_bandwidth, dtype=np.float64)
    bandwidth = min_bw + _shares(spend_bw, n) * (
        total_bandwidth - min_bw * n)
    return units, bandwidth


def qos_allocate(
    curves: np.ndarray,
    bw_delay: np.ndarray,
    slowdown: np.ndarray,
    *,
    min_ways,
    total_units: int,
    min_bandwidth,
    total_bandwidth: float,
    bound: float = QOS_SLOWDOWN_BOUND,
    gain: float = QOS_VIOLATION_GAIN,
) -> Tuple[np.ndarray, np.ndarray]:
    """QoS-constrained allocation (numpy golden).

    Throughput-maximizing demand-proportional shares (marginal ATD hits
    for cache, accumulated delay for bandwidth), with the weight of any
    client violating its slowdown bound boosted by ``1 + gain *
    violation`` — resources flow to the constraint violators until their
    slowdown drops back under the bound.  ``slowdown`` is each client's
    first-interval (equal-share) reference IPC over its current IPC.
    """
    n = bw_delay.shape[-1]
    mw = _per_client(min_ways, bw_delay).astype(np.float64)
    boost = 1.0 + gain * np.maximum(slowdown - bound, 0.0)
    cache_w = _cache_desire(curves, mw) * boost
    bw_w = bw_delay * boost

    target = mw + _shares(cache_w, n) * (
        total_units - mw.sum(axis=-1, keepdims=True))
    units = largest_remainder_round(target, total_units)
    min_bw = np.asarray(min_bandwidth, dtype=np.float64)
    bandwidth = min_bw + _shares(bw_w, n) * (total_bandwidth - min_bw * n)
    return units, bandwidth


# --------------------------------------------------------------------- #
# the registered families
# --------------------------------------------------------------------- #

def _grid(**kwargs) -> Dict[str, object]:
    return kwargs


# Classic Table-3 mode combinations (the paper's comparison menu).
register(PolicyFamily(
    "baseline",
    modes=(Mode.UNPARTITIONED, Mode.UNPARTITIONED, PrefetchMode.OFF),
    static_grid=_grid()))
register(PolicyFamily(
    "equal off",
    modes=(Mode.EQUAL, Mode.EQUAL, PrefetchMode.OFF),
    static_grid=_grid()))
register(PolicyFamily(
    "equal on",
    modes=(Mode.EQUAL, Mode.EQUAL, PrefetchMode.ON),
    static_grid=_grid(pf_all_on=True)))
register(PolicyFamily(
    "only cache",
    modes=(Mode.DYNAMIC, Mode.UNPARTITIONED, PrefetchMode.OFF),
    static_grid=_grid(manage_cache=True)))
register(PolicyFamily(
    "only bw",
    modes=(Mode.UNPARTITIONED, Mode.DYNAMIC, PrefetchMode.OFF),
    static_grid=_grid(manage_bw=True)))
register(PolicyFamily(
    "only pref",
    modes=(Mode.UNPARTITIONED, Mode.UNPARTITIONED, PrefetchMode.DYNAMIC),
    static_grid=_grid(manage_pf=True)))
register(PolicyFamily(
    "bw+pref",
    modes=(Mode.UNPARTITIONED, Mode.DYNAMIC, PrefetchMode.DYNAMIC),
    static_grid=_grid(manage_bw=True, manage_pf=True)))
register(PolicyFamily(
    "bw+cache",
    modes=(Mode.DYNAMIC, Mode.DYNAMIC, PrefetchMode.OFF),
    static_grid=_grid(manage_cache=True, manage_bw=True)))
register(PolicyFamily(
    "cache+pref",
    modes=(Mode.DYNAMIC, Mode.UNPARTITIONED, PrefetchMode.DYNAMIC),
    static_grid=_grid(manage_cache=True, manage_pf=True)))
register(PolicyFamily(
    "CPpf",
    variant="cppf",
    static_grid=_grid(manage_cache=True, pf_all_on=True)))
register(PolicyFamily(
    "CBP",
    modes=(Mode.DYNAMIC, Mode.DYNAMIC, PrefetchMode.DYNAMIC),
    static_grid=_grid(manage_cache=True, manage_bw=True, manage_pf=True)))

# New families from related work (ROADMAP item 4), ridden on the same
# stacked manager axis.
register(PolicyFamily(
    "auction",
    cache_policy=CACHE_AUCTION,
    bw_policy=BW_AUCTION,
    static_grid=_grid(manage_cache=True, manage_bw=True)))
register(PolicyFamily(
    "qos",
    cache_policy=CACHE_QOS,
    bw_policy=BW_QOS,
    static_grid=_grid(manage_cache=True, manage_bw=True)))
register(PolicyFamily(
    "bank bw",
    bandwidth_banks=4,
    static_grid=_grid(manage_bw=True, bandwidth_banks=4)))
