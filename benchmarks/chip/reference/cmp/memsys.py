"""Interval performance model of the 16-core tiled CMP (paper §4, Table 1).

This is the evaluation *plant* for the faithful reproduction: a steady-state
analytic model with the same signal structure the paper's controllers
consume — per-application miss curves (ATD), memory queuing delays, and IPC
under a given (cache, bandwidth, prefetch) allocation.

Model structure (per application i):

  CPI_i  = cpi_base_i + exposed_mpki_i / 1000 * miss_penalty_i
  miss_penalty_i = (DRAM_latency + queuing_delay_i) * freq / mlp_i
  queuing_delay_i = Q_SCALE * rho_i / (1 - rho_i)          (M/M/1-shaped)
  rho_i = traffic_i / bandwidth_i                (partitioned: own channel)
        = sum(traffic) / total_bandwidth         (unpartitioned: shared queue)
  traffic_i = IPC_i * freq * reqki_i / 1000 * 64 B

with prefetching folding in as: covered misses are (partially) hidden,
useless prefetches add traffic, pollution shrinks the effective allocation
(paper §2.2 observations 2-4).  Unpartitioned cache is modelled as
access-rate-proportional LRU occupancy (high-APKI applications steal space —
the contention CBP's cache partitioning removes).  IPC <-> traffic <->
queuing is a fixed point, solved by damped iteration; a bandwidth cap
bounds IPC when a partition saturates (observation 5's "cost of a miss is
much higher in the case of lower bandwidth allocation").

Everything is vectorized over a leading batch dimension so the Fig. 5
exhaustive search (~10^5 configurations x 640 workloads) runs as one
broadcasted evaluation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .apps import AppArrays

FREQ_GHZ = 4.0            # paper Table 1: 4 GHz cores
DRAM_LAT_NS = 80.0        # paper Table 1: 80 ns memory latency
LINE_BYTES = 64.0
Q_SCALE_NS = 42.0         # queuing-delay scale (calibrated)
IF_SKEW = 0.8             # shared-queue unfairness: low-traffic clients wait
                          # behind streaming bursts (FR-FCFS-like skew)
PF_QUEUE_WEIGHT = 0.55     # prefetch fills are issued off the critical path
                          # (deprioritized by the MC): they consume bandwidth
                          # (cap) but add little demand-queue delay
RHO_MAX = 0.98            # queue stability clip
FIXED_POINT_ITERS = 60
DAMPING = 0.5
BANK_SKEW = 0.6           # banked-token mode: per-bank access affinity decay
                          # (each client concentrates on "its" banks; row-
                          # buffer locality makes the spread geometric)
DEFAULT_BANDWIDTH_BANKS = 4

#: Precision of the model arithmetic.  float64 is what the configuration
#: states; the benchmark's precision control sets float32 here.
DTYPE = np.float64


def _cast(apps: AppArrays) -> AppArrays:
    return dataclasses.replace(apps, **{
        f.name: np.asarray(getattr(apps, f.name), dtype=DTYPE)
        for f in dataclasses.fields(apps) if f.name != "names"})


@dataclasses.dataclass
class SteadyState:
    """Model outputs for one (workload, allocation) evaluation."""

    ipc: np.ndarray            # (..., n)
    queuing_delay_ns: np.ndarray
    traffic_gbps: np.ndarray
    mpki: np.ndarray           # effective demand MPKI (post-prefetch-pollution)
    exposed_mpki: np.ndarray   # misses whose latency the core actually eats
    occupancy_units: np.ndarray  # effective cache units used


def mpki_curve(apps: AppArrays, units: np.ndarray) -> np.ndarray:
    """Miss curve: MPKI as a function of allocated units (32 kB each).

    Defined for real-valued ``units`` (unpartitioned occupancy is
    fractional).  Below the 4-unit reference point the curve continues to
    rise smoothly.
    """
    u = np.maximum(np.asarray(units, dtype=DTYPE), 1.0)
    span = apps.mpki_min_alloc - apps.mpki_floor
    return apps.mpki_floor + span * np.exp(-(u - 4.0) / apps.ws_units)


def bank_affinity(n_apps: int, n_banks: int) -> np.ndarray:
    """Per-(client, bank) access affinity for the banked-token mode.

    Client i concentrates geometrically (``BANK_SKEW``) on bank
    ``(i + b) % n_banks`` order — a stand-in for address-interleaving +
    row-buffer locality — normalized so each client's affinities sum to 1.
    For ``n_banks == 1`` this is exactly 1.0 (skew**0 / 1.0), which makes
    the banked formulas reduce BIT-identically to the flat partitioned
    channel model.
    """
    i = np.arange(n_apps, dtype=np.float64)[:, None]
    b = np.arange(n_banks, dtype=np.float64)[None, :]
    a = BANK_SKEW ** np.mod(i + b, float(n_banks))
    return a / a.sum(axis=-1, keepdims=True)


def evaluate(
    apps: AppArrays,
    cache_units: np.ndarray,
    bandwidth_gbps: np.ndarray,
    prefetch_on: np.ndarray,
    *,
    cache_partitioned: bool = True,
    bandwidth_partitioned: bool = True,
    total_cache_units: float = 256.0,
    total_bandwidth_gbps: float = 64.0,
    llc_extra_cycles: float = 0.0,
    bandwidth_banks: int = 1,
    iters: int = FIXED_POINT_ITERS,
) -> SteadyState:
    """Solve the IPC <-> traffic <-> queuing fixed point.

    All array arguments broadcast against shape (..., n) where n = #apps.
    ``cache_units``/``bandwidth_gbps`` are ignored for the dimensions that
    are unpartitioned (the shared model applies instead).

    ``bandwidth_banks > 1`` switches the partitioned-bandwidth regime to
    per-bank tokens (arxiv 2410.14003): each client's allocation is split
    evenly across banks, its traffic spreads by :func:`bank_affinity`, and
    queuing is the affinity-weighted sum of per-bank M/M/1 delays — a hot
    bank saturates before the client's aggregate allocation does.  The
    flat partitioned model is the exact 1-bank special case.
    """
    apps = _cast(apps)
    cache_units = np.asarray(cache_units, dtype=DTYPE)
    bw = np.asarray(bandwidth_gbps, dtype=DTYPE)
    pf = np.asarray(prefetch_on, dtype=DTYPE)

    ipc = 1.0 / np.broadcast_to(
        apps.cpi_base, np.broadcast_shapes(
            cache_units.shape, bw.shape, pf.shape, apps.cpi_base.shape)
    ).copy()

    q_ns = np.zeros_like(ipc)
    traffic = np.zeros_like(ipc)
    mpki_eff = np.zeros_like(ipc)
    exposed = np.zeros_like(ipc)
    occ = np.zeros_like(ipc)

    for _ in range(iters):
        # ---- cache occupancy -------------------------------------------- #
        if cache_partitioned:
            occ = np.broadcast_to(cache_units, ipc.shape).astype(DTYPE)
        else:
            # Shared LRU: occupancy ~ insertion-rate share (misses/sec).
            # Fixed point: occupancy depends on miss rate depends on
            # occupancy — resolved by the outer iteration.
            miss_rate = np.maximum(mpki_eff, 1e-3) * ipc  # misses/cycle*1e3
            share = miss_rate / np.sum(miss_rate, axis=-1, keepdims=True)
            occ = share * total_cache_units
        occ_eff = np.maximum(occ - apps.pf_pollution * pf, 1.0)

        # ---- prefetch-adjusted miss stream ------------------------------- #
        m = mpki_curve(apps, occ_eff)
        mpki_eff = m
        covered = apps.pf_cov * pf * m
        exposed = m - covered * apps.pf_hide
        useless = covered * (1.0 / np.maximum(apps.pf_acc, 1e-3) - 1.0)
        reqki = m * (1.0 + apps.wb_frac) + useless
        # Demand-critical request stream: prefetch fills (covered misses
        # fetched early + useless prefetches) are deprioritized by the
        # memory controller, so they barely lengthen the queue that demand
        # misses wait in — but they do consume channel bandwidth (cap).
        reqki_q = ((m - covered) + m * apps.wb_frac
                   + PF_QUEUE_WEIGHT * (covered + useless))

        # ---- memory queuing ---------------------------------------------- #
        traffic = ipc * FREQ_GHZ * reqki * LINE_BYTES / 1000.0  # GB/s
        traffic_q = ipc * FREQ_GHZ * reqki_q * LINE_BYTES / 1000.0
        if bandwidth_partitioned and bandwidth_banks > 1:
            # Banked tokens: affinity-weighted per-bank M/M/1 queues; the
            # effective cap is set by the first bank a client saturates.
            aff = bank_affinity(traffic_q.shape[-1], bandwidth_banks)
            bank_bw = bw[..., None] / float(bandwidth_banks)
            rho_b = traffic_q[..., None] * aff / np.maximum(bank_bw, 1e-6)
            rho_cb = np.clip(rho_b, 0.0, RHO_MAX)
            q_bank = Q_SCALE_NS * rho_cb / (1.0 - rho_cb)
            q_ns = np.sum(aff * q_bank, axis=-1)
            cap_gbps = np.min(bank_bw / aff, axis=-1)
        elif bandwidth_partitioned:
            rho = traffic_q / np.maximum(bw, 1e-6)
            cap_gbps = bw
        else:
            tot = np.sum(traffic_q, axis=-1, keepdims=True)
            rho = np.broadcast_to(
                tot / total_bandwidth_gbps, traffic_q.shape)
            # Unpartitioned: an app can use up to the whole pipe, but the
            # aggregate is capped — model per-app cap as proportional share
            # of the total when saturated.
            tot_full = np.sum(traffic, axis=-1, keepdims=True)
            with np.errstate(invalid="ignore", divide="ignore"):
                frac = np.where(tot_full > 0, traffic / tot_full,
                                1.0 / traffic.shape[-1])
            cap_gbps = frac * total_bandwidth_gbps
        if not (bandwidth_partitioned and bandwidth_banks > 1):
            rho_c = np.clip(rho, 0.0, RHO_MAX)
            q_ns = Q_SCALE_NS * rho_c / (1.0 - rho_c)
        if not bandwidth_partitioned:
            # FR-FCFS-style unfairness: clients with a small share of the
            # traffic wait behind other clients' bursts; heavy streaming
            # clients ride their own row hits.  Partitioning (MBA-like
            # virtual channels) removes exactly this term — it is the
            # interference the paper's bandwidth controller targets.
            q_ns = q_ns * (1.0 + IF_SKEW * (1.0 - frac))

        # ---- IPC ---------------------------------------------------------- #
        penalty_cyc = (DRAM_LAT_NS + q_ns) * FREQ_GHZ / apps.mlp
        # Larger LLCs cost extra access latency on every LLC access
        # (CACTI scaling — the paper's Fig. 12b effect).
        cpi = (apps.cpi_base + apps.apki / 1000.0 * llc_extra_cycles
               + exposed / 1000.0 * penalty_cyc)
        ipc_demand = 1.0 / cpi
        # Bandwidth cap: IPC such that traffic <= RHO_MAX * cap.
        ipc_cap = RHO_MAX * cap_gbps / np.maximum(
            FREQ_GHZ * reqki * LINE_BYTES / 1000.0, 1e-9)
        ipc_new = np.minimum(ipc_demand, ipc_cap)
        ipc = DAMPING * ipc + (1.0 - DAMPING) * ipc_new

    return SteadyState(
        ipc=ipc, queuing_delay_ns=q_ns, traffic_gbps=traffic,
        mpki=mpki_eff, exposed_mpki=exposed, occupancy_units=occ)


def utility_curves(
    apps: AppArrays,
    prefetch_on: np.ndarray,
    ipc: np.ndarray,
    total_units: int,
    duration_ms: float = 1.0,
) -> np.ndarray:
    """ATD measurement: hits(u) for u in 0..total_units per app.

    Paper interaction #5: when prefetching is on, prefetched lines appear as
    ATD hits regardless of allocation, flattening the utility curve — the
    cache controller then assigns less space to prefetch-friendly apps.
    """
    apps = _cast(apps)
    u = np.arange(total_units + 1, dtype=DTYPE)
    m = mpki_curve(
        dataclasses.replace(apps),  # same params
        u[:, None] - apps.pf_pollution[None, :] * prefetch_on[None, :],
    )  # (U+1, n)
    m = np.moveaxis(m, 0, -1)  # (n, U+1)
    pf = np.asarray(prefetch_on, dtype=DTYPE)[..., None]
    eff_miss = m * (1.0 - apps.pf_cov[:, None] * pf)
    hits = np.maximum(apps.apki[:, None] - eff_miss, 0.0)
    kilo_instr = (np.asarray(ipc)[..., None] * FREQ_GHZ * 1e6 * duration_ms
                  / 1000.0)
    return hits * kilo_instr
