"""Bandwidth-allocation controller (paper §3.2.2, Algorithm 1).

Partitions total bandwidth proportionally to the per-client memory queuing
delay observed in the previous interval: clients that waited longer get more.
Every client first receives ``min_bandwidth_allocation`` ("in order to avoid
unfairly giving a very low allocation to applications with a small queuing
delay"); the remainder is split pro-rata by accumulated delay.

:func:`allocate_bandwidth` is the numpy golden reference;
:func:`allocate_bandwidth_jax` is the traced mirror used inside the fused
Fig. 8 timeline (:mod:`repro.sim.timeline_jax`).  The ``min_allocation * n
> total`` feasibility check is deliberately hoisted out of the traced
mirror — callers validate once on the host (:func:`check_bandwidth_floor`)
before compiling a timeline.
"""
from __future__ import annotations

import numpy as np


def allocate_bandwidth(
    queuing_delay: np.ndarray,
    total_bandwidth: float,
    min_allocation: float,
) -> np.ndarray:
    """Algorithm 1, verbatim, vectorized over leading batch axes.

    Args:
      queuing_delay: (..., n) accumulated per-client queuing delays (any
        unit — only proportions matter).  Leading axes (e.g. the sweep
        runner's mix axis) each get an independent allocation.
      total_bandwidth: capacity to distribute (GB/s).
      min_allocation: per-client floor (GB/s) — a scalar, or an array
        broadcastable against the leading batch axes (shape ``(..., 1)``),
        which is how ``run_sweep(param_grid=...)`` batches over
        ``CBPParams.min_bandwidth_allocation``.

    Returns:
      (..., n) float allocation summing to ``total_bandwidth`` per batch.
    """
    delay = np.asarray(queuing_delay, dtype=np.float64)
    n = delay.shape[-1]
    min_alloc = np.asarray(min_allocation, dtype=np.float64)
    check_bandwidth_floor(min_alloc, n, total_bandwidth)

    # line 2: remaining after floors (line 5: every client gets the floor)
    remaining = total_bandwidth - min_alloc * n

    total_delay = delay.sum(axis=-1, keepdims=True)  # line 4
    # lines 7-9: proportional share of the remainder; no one queued ->
    # split the remainder evenly.
    share = np.where(total_delay > 0,
                     delay / np.where(total_delay > 0, total_delay, 1.0),
                     1.0 / n)
    return min_alloc + share * remaining


def check_bandwidth_floor(min_allocation, n_clients: int,
                          total_bandwidth: float) -> None:
    """Host-side feasibility check for Algorithm 1 (raises ``ValueError``).

    Kept out of the traced :func:`allocate_bandwidth_jax` so the fused
    timeline validates once per program instead of per segment.
    """
    if np.any(np.asarray(min_allocation, dtype=np.float64) * n_clients
              > total_bandwidth):
        raise ValueError("min_allocation * n exceeds total bandwidth")


class BandwidthController:
    """Stateful wrapper: accumulates delays across intervals (paper §3.3,

    "per application queuing delays are accumulated with those from the
    previous interval"), with a decay factor so stale phases wash out.
    """

    def __init__(self, total_bandwidth: float, min_allocation: float,
                 decay: float = 0.5):
        self.total_bandwidth = total_bandwidth
        self.min_allocation = min_allocation
        self.decay = decay
        self._acc: np.ndarray | None = None

    def observe(self, queuing_delay: np.ndarray) -> None:
        delay = np.asarray(queuing_delay, dtype=np.float64)
        if self._acc is None:
            self._acc = delay.copy()
        else:
            self._acc = self.decay * self._acc + delay

    def allocate(self) -> np.ndarray:
        if self._acc is None:
            raise RuntimeError("no delays observed yet")
        return allocate_bandwidth(
            self._acc, self.total_bandwidth, self.min_allocation)
