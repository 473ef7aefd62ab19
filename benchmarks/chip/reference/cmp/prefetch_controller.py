"""Prefetch-throttling controller (paper §3.2.3, Algorithm 2).

Samples performance (IPC in the CMP model; tokens/sec or 1/step-time in the
TPU binding) with the prefetcher enabled and disabled over
``prefetch_sampling_period`` each, then enables prefetching for the next
``prefetch_interval`` iff the measured speedup exceeds
``speedup_threshold``.  "The prefetch throttling controller is generic enough
to support any type of prefetcher" — here it is generic over what "prefetch"
means (hardware stride prefetcher, input-pipeline depth, kernel
double-buffering, KV-page readahead).
"""
from __future__ import annotations

import numpy as np


def throttle_decision(
    perf_with: np.ndarray,
    perf_without: np.ndarray,
    speedup_threshold: float = 1.05,
) -> np.ndarray:
    """Algorithm 2: enable iff speedup > threshold.

    Args:
      perf_with: (..., n) performance sampled with prefetching enabled.
      perf_without: (..., n) performance sampled with prefetching disabled.
      speedup_threshold: paper default 1.05; may be an array broadcastable
        against the leading batch axes (shape ``(..., 1)``) so
        ``run_sweep(param_grid=...)`` can batch over it.

    Returns:
      (..., n) bool — prefetcher setting for the next prefetch interval.
    """
    w = np.asarray(perf_with, dtype=np.float64)
    wo = np.asarray(perf_without, dtype=np.float64)
    speedup = np.where(wo > 0, w / np.maximum(wo, 1e-12), 1.0)
    return speedup > speedup_threshold  # lines 3-6


class PrefetchController:
    """Stateful wrapper tracking the current per-client setting."""

    def __init__(self, n_clients: int, speedup_threshold: float = 1.05):
        self.speedup_threshold = speedup_threshold
        self.enabled = np.zeros(n_clients, dtype=bool)
        self.last_speedup = np.ones(n_clients, dtype=np.float64)

    def update(self, perf_with: np.ndarray,
               perf_without: np.ndarray) -> np.ndarray:
        w = np.asarray(perf_with, dtype=np.float64)
        wo = np.asarray(perf_without, dtype=np.float64)
        self.last_speedup = np.where(wo > 0, w / np.maximum(wo, 1e-12), 1.0)
        self.enabled = throttle_decision(w, wo, self.speedup_threshold)
        return self.enabled
