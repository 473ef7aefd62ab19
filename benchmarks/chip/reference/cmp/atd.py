"""Auxiliary Tag Directory (ATD) machinery (paper §3.2.1 / §3.4).

The paper uses sampled ATDs [Qureshi & Patt, MICRO'06 "UMON"] to estimate,
per application, how many misses would be avoided with additional cache ways.
Two implementations are provided:

* :class:`SampledATD` — the counter container used by the cache-allocation
  controller.  The *plant* (CMP model or KV pool) feeds it per-interval
  utility measurements; counters are halved after every reconfiguration
  (paper §3.3, "The ATD values will be halved after each reconfiguration").

* :class:`StackDistanceMonitor` — an online LRU stack-distance histogram.
  This is the software ATD used by the TPU binding (``repro.serving``): each
  KV-pool client records page accesses, and the histogram converts directly
  into a hits-vs-pages utility curve, exactly like UMON-global.
"""
from __future__ import annotations

from typing import Dict, Hashable, List

import numpy as np


class SampledATD:
    """Per-client utility counters with reconfiguration-time halving."""

    def __init__(self, n_clients: int, total_units: int):
        self.n_clients = n_clients
        self.total_units = total_units
        self._counters = np.zeros((n_clients, total_units + 1), dtype=np.float64)

    def record(self, utility_curves: np.ndarray) -> None:
        """Accumulate an interval's hits-vs-units measurement.

        ``utility_curves[i, u]`` = hits client ``i`` would have observed with
        ``u`` units during the interval.  Curves must be non-decreasing in
        ``u`` (more cache never yields fewer hits under LRU inclusion).
        """
        curves = np.asarray(utility_curves, dtype=np.float64)
        if curves.shape != self._counters.shape:
            raise ValueError(
                f"expected {self._counters.shape}, got {curves.shape}")
        self._counters += curves

    def halve(self, decay: float = 0.5) -> None:
        """Decay history so recent behaviour dominates (paper §3.3).

        ``decay`` defaults to the paper's halving; callers wire it from
        ``CBPParams.atd_decay`` so the constant is sweepable.
        """
        self._counters *= decay

    def utility_curves(self) -> np.ndarray:
        """Current hits-vs-units estimate, shape (n_clients, units + 1)."""
        return self._counters.copy()

    def reset(self) -> None:
        self._counters[:] = 0.0


class StackDistanceMonitor:
    """Online LRU stack-distance histogram over an access stream.

    ``access(key)`` returns the LRU stack distance of ``key`` (0 == MRU hit,
    ``inf``/``capacity`` == cold miss) and updates the recency stack.  The
    histogram then answers: *with c units of cache, how many of the observed
    accesses would have hit?* — which is precisely the utility curve the
    Lookahead allocator consumes.
    """

    def __init__(self, max_units: int):
        self.max_units = max_units
        self._stack: List[Hashable] = []      # index 0 == MRU
        self._pos: Dict[Hashable, int] = {}   # key -> stack index (lazy)
        self._hist = np.zeros(max_units + 1, dtype=np.float64)  # [d] counts
        self._cold = 0.0
        self._accesses = 0.0

    def access(self, key: Hashable) -> int:
        self._accesses += 1
        try:
            depth = self._stack.index(key)
        except ValueError:
            depth = -1
        if depth < 0:
            self._cold += 1
            self._stack.insert(0, key)
            if len(self._stack) > self.max_units:
                self._stack.pop()
            return self.max_units
        # Hit at stack distance `depth`: with > depth units it would hit.
        if depth < len(self._hist):
            self._hist[depth] += 1
        else:
            self._cold += 1
        self._stack.pop(depth)
        self._stack.insert(0, key)
        return depth

    def utility_curve(self) -> np.ndarray:
        """hits(u) for u in 0..max_units (non-decreasing)."""
        hits = np.zeros(self.max_units + 1, dtype=np.float64)
        np.cumsum(self._hist[:-1], out=hits[1:])
        return hits

    def halve(self) -> None:
        self._hist *= 0.5
        self._cold *= 0.5
        self._accesses *= 0.5

    @property
    def accesses(self) -> float:
        return self._accesses
