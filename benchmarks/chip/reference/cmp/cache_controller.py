"""Cache-allocation controller: UCP Lookahead (paper §3.2.1).

The controller consumes per-client *utility curves* — hits as a function of
allocated units, measured by the ATD — and produces an integer allocation
that greedily maximizes marginal utility (misses avoided per unit), exactly
as in Qureshi & Patt's Lookahead algorithm.  A ``min_units`` floor is applied
before distribution to adapt to an inclusive hierarchy (paper: "we assign a
minimum allocation of cache space (min_ways) to all the applications before
distributing the remaining capacity").

This module is the **numpy golden reference**.  The batched, jitted port
lives in :mod:`repro.core.cache_controller_jax` and must match it
bit-identically away from tie knife-edges; :class:`CacheController`
dispatches between the two via ``backend="numpy"|"jax"`` (mirroring
``CMPConfig.backend``).

Deterministic tie-breaks (shared by both backends):

* among clients with equal best marginal utility, the lowest index wins;
* within a client, the smallest step ``k`` achieving the best utility wins;
* the zero-utility spread orders clients by remaining potential gain with a
  *stable* sort, so equal-gain clients stay in index order.

``lookahead_allocate`` increments a module-level call counter so tests and
the CI sweep smoke can assert that device-resident sweeps perform **zero**
per-mix host allocator calls (see :func:`allocator_calls`).
"""
from __future__ import annotations

import numpy as np

#: Number of times the numpy ``lookahead_allocate`` has run in this process.
#: The batched JAX path never touches it, which is what the device-resident
#: sweep smoke asserts.
_ALLOCATOR_CALLS = 0


def allocator_calls() -> int:
    """Total numpy ``lookahead_allocate`` invocations so far."""
    return _ALLOCATOR_CALLS


def reset_allocator_calls() -> None:
    global _ALLOCATOR_CALLS
    _ALLOCATOR_CALLS = 0


def _max_marginal_utility(curve: np.ndarray, have: int, balance: int):
    """Lookahead's get_max_mu: best (utility/units) step from ``have``.

    Returns ``(mu, k)`` where ``k`` maximizes
    ``(curve[have + k] - curve[have]) / k`` over ``1 <= k <= balance``.
    """
    top = min(have + balance, len(curve) - 1)
    if top <= have:
        return 0.0, 0
    ks = np.arange(1, top - have + 1)
    gains = curve[have + 1: top + 1] - curve[have]
    mus = gains / ks
    best = int(np.argmax(mus))
    return float(mus[best]), int(ks[best])


def lookahead_allocate(
    utility_curves: np.ndarray,
    total_units: int,
    min_units: int = 4,
) -> np.ndarray:
    """Allocate ``total_units`` among clients by greedy marginal utility.

    Args:
      utility_curves: (n, total_units + 1); ``[i, u]`` = hits for client ``i``
        with ``u`` units.  Need not be normalized; only differences matter.
      total_units: capacity to distribute (e.g. 256 x 32 kB = 8 MB).
      min_units: floor per client (paper's ``min_ways``).

    Returns:
      (n,) int allocation summing exactly to ``total_units``.
    """
    global _ALLOCATOR_CALLS
    _ALLOCATOR_CALLS += 1
    curves = np.asarray(utility_curves, dtype=np.float64)
    n = curves.shape[0]
    if curves.shape[1] != total_units + 1:
        raise ValueError(
            f"utility curves must have {total_units + 1} points, "
            f"got {curves.shape[1]}")
    if n * min_units > total_units:
        raise ValueError("min_units * n exceeds capacity")

    alloc = np.full(n, min_units, dtype=np.int64)
    balance = total_units - int(alloc.sum())

    while balance > 0:
        best_mu = -1.0
        best_i = -1
        best_k = 0
        for i in range(n):
            mu, k = _max_marginal_utility(curves[i], int(alloc[i]), balance)
            if k > 0 and mu > best_mu:
                best_mu, best_i, best_k = mu, i, k
        if best_i < 0 or best_mu <= 0.0:
            # No client gains from more cache: spread the remainder evenly
            # (UCP leaves no capacity idle).  Stable sort: equal remaining
            # gains keep index order (the documented tie-break, which the
            # JAX port reproduces).
            order = np.argsort(
                -(curves[:, -1] - curves[np.arange(n), alloc]),
                kind="stable")
            j = 0
            while balance > 0:
                i = int(order[j % n])
                if alloc[i] < total_units:
                    alloc[i] += 1
                    balance -= 1
                j += 1
            break
        alloc[best_i] += best_k
        balance -= best_k

    assert int(alloc.sum()) == total_units
    return alloc


def cppf_allocate(
    utility_curves: np.ndarray,
    total_units: int,
    min_units: int,
    active: np.ndarray,
) -> np.ndarray:
    """CPpf allocation (paper §4.4): pin inactive clients at ``min_units``,
    UCP over the remaining capacity for the active ones.

    ``active`` marks the clients that compete for capacity (the
    prefetch-UNfriendly ones in CPpf; friendly apps take the minimum
    partition because prefetching offsets it).  With no active client the
    capacity is split evenly, distributing the remainder to the
    lowest-index clients so no unit is dropped.

    Args:
      utility_curves: (n, total_units + 1) as in :func:`lookahead_allocate`.
      total_units: capacity to distribute.
      min_units: per-client floor / pinned allocation.
      active: (n,) bool mask of clients that compete for capacity.

    Returns:
      (n,) int allocation summing exactly to ``total_units``.
    """
    curves = np.asarray(utility_curves, dtype=np.float64)
    active = np.asarray(active, dtype=bool)
    n = curves.shape[0]
    units = np.full(n, min_units, dtype=np.int64)
    others = np.where(active)[0]
    remaining = total_units - min_units * int((~active).sum())
    if len(others) > 0:
        units[others] = lookahead_allocate(
            curves[others][:, : remaining + 1], remaining, min_units)
    else:
        extra = total_units - n * min_units
        units += extra // n
        units[: extra % n] += 1
    assert int(units.sum()) == total_units
    return units


class CacheController:
    """Backend-dispatched Lookahead allocator (numpy | JAX | Pallas).

    ``allocate`` accepts utility curves with arbitrary leading batch axes
    ``(..., n, total_units + 1)`` and returns ``(..., n)`` integer
    allocations.  The numpy backend loops the golden-reference greedy over
    the batch on the host; the JAX backend runs the whole batch as one
    jitted device call (:mod:`repro.core.cache_controller_jax`), which is
    what keeps full sweeps device-resident; the Pallas backend swaps the
    batched while_loop for the per-row VMEM-resident kernel
    (:mod:`repro.kernels.lookahead_greedy`, interpret mode off-TPU) behind
    the same entry points.
    """

    def __init__(self, total_units: int, min_units: int = 4,
                 backend: str = "numpy"):
        if backend != "numpy":
            raise ValueError(f"the reference allocator is numpy only, "
                             f"not {backend!r}")
        self.total_units = total_units
        self.min_units = min_units
        self.backend = backend

    def _min_units_array(self, min_units, batch_shape):
        mu = self.min_units if min_units is None else min_units
        return np.broadcast_to(
            np.asarray(mu, dtype=np.int64), batch_shape)

    def allocate(self, utility_curves: np.ndarray,
                 min_units=None) -> np.ndarray:
        """Lookahead over ``(..., n, U+1)`` curves -> ``(..., n)`` ints.

        ``min_units`` may override the configured floor, either as a scalar
        or per-batch-element (broadcast against the leading axes) — the
        sweep runner uses this to batch over ``CBPParams.min_ways``.
        """
        curves = np.asarray(utility_curves, dtype=np.float64)
        batch_shape = curves.shape[:-2]
        mus = self._min_units_array(min_units, batch_shape)
        if curves.ndim == 2:
            return lookahead_allocate(curves, self.total_units, int(mus))
        out = np.empty(curves.shape[:-1], dtype=np.int64)
        for idx in np.ndindex(*batch_shape):
            out[idx] = lookahead_allocate(
                curves[idx], self.total_units, int(mus[idx]))
        return out

    def allocate_masked(self, utility_curves: np.ndarray,
                        active: np.ndarray, min_units=None) -> np.ndarray:
        """CPpf-style allocation over ``(..., n, U+1)`` curves.

        ``active`` is ``(..., n)`` bool; inactive clients are pinned at the
        floor and the rest of the capacity is UCP-partitioned among the
        active ones (see :func:`cppf_allocate`).
        """
        curves = np.asarray(utility_curves, dtype=np.float64)
        active = np.asarray(active, dtype=bool)
        batch_shape = curves.shape[:-2]
        mus = self._min_units_array(min_units, batch_shape)
        if curves.ndim == 2:
            return cppf_allocate(curves, self.total_units, int(mus), active)
        out = np.empty(curves.shape[:-1], dtype=np.int64)
        for idx in np.ndindex(*batch_shape):
            out[idx] = cppf_allocate(
                curves[idx], self.total_units, int(mus[idx]), active[idx])
        return out
