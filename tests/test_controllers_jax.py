"""JAX-vs-numpy parity for the traced controller ports (PR 3).

The fused Fig. 8 timeline (``repro.sim.timeline_jax``) runs Algorithm 1
(:func:`repro.core.allocate_bandwidth_jax`) and Algorithm 2
(:func:`repro.core.throttle_decision_jax`) inside the jitted scan; these
property tests pin them to the numpy golden references, including the
batched ``(..., 1)`` per-row ``min_allocation`` / ``speedup_threshold``
forms used by ``run_sweep(param_grid=...)`` and the no-delay even-split
branch.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    allocate_bandwidth,
    allocate_bandwidth_jax,
    check_bandwidth_floor,
    throttle_decision,
    throttle_decision_jax,
)
from repro.core.x64 import x64_context


def _bw_jax(delay, total, min_alloc):
    with x64_context():
        import jax.numpy as jnp
        return np.asarray(allocate_bandwidth_jax(
            jnp.asarray(delay, dtype=jnp.float64), total, min_alloc))


def _throttle_jax(w, wo, thr):
    with x64_context():
        import jax.numpy as jnp
        return np.asarray(throttle_decision_jax(
            jnp.asarray(w, dtype=jnp.float64),
            jnp.asarray(wo, dtype=jnp.float64), thr))


# --------------------------------------------------------------------- #
# Algorithm 1: bandwidth partitioning
# --------------------------------------------------------------------- #


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 16),
    total=st.floats(16.0, 128.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_bandwidth_jax_matches_numpy(n, total, seed):
    rng = np.random.default_rng(seed)
    delay = rng.uniform(0.0, 100.0, size=(3, n))  # leading batch axis
    min_alloc = float(rng.uniform(0.0, total / n))
    ref = allocate_bandwidth(delay, total, min_alloc)
    jx = _bw_jax(delay, total, min_alloc)
    np.testing.assert_allclose(jx, ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(jx.sum(axis=-1), total, rtol=1e-12)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_bandwidth_jax_batched_min_allocation_rows(seed):
    """(P, 1) per-row floors — the param_grid batching form."""
    rng = np.random.default_rng(seed)
    P, M, n = 3, 4, 8
    total = 64.0
    delay = rng.uniform(0.0, 50.0, size=(P, M, n))
    min_rows = rng.uniform(0.0, total / n, size=(P, 1, 1))
    ref = allocate_bandwidth(delay, total, min_rows)
    jx = _bw_jax(delay, total, min_rows)
    np.testing.assert_allclose(jx, ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(jx.sum(axis=-1), total, rtol=1e-12)


def test_bandwidth_jax_no_delay_even_split():
    """No one queued -> the remainder splits evenly (Algorithm 1 line 8)."""
    jx = _bw_jax(np.zeros((2, 4)), 64.0, 1.0)
    np.testing.assert_allclose(jx, np.full((2, 4), 16.0))
    # ...and a single all-zero row inside a mixed batch takes the same
    # branch while the other rows stay proportional.
    delay = np.stack([np.zeros(4), np.array([3.0, 1.0, 0.0, 0.0])])
    ref = allocate_bandwidth(delay, 16.0, 1.0)
    np.testing.assert_allclose(_bw_jax(delay, 16.0, 1.0), ref, rtol=1e-12)


def test_bandwidth_floor_check_is_hoisted():
    """The traced mirror skips validation; the host check must raise."""
    with pytest.raises(ValueError):
        check_bandwidth_floor(9.0, 8, 64.0)
    with pytest.raises(ValueError):
        allocate_bandwidth(np.ones(8), 64.0, 9.0)
    # per-row floors: any infeasible row trips the check
    with pytest.raises(ValueError):
        check_bandwidth_floor(np.array([[1.0], [9.0]]), 8, 64.0)


# --------------------------------------------------------------------- #
# Algorithm 2: prefetch throttling
# --------------------------------------------------------------------- #


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 16),
    thr=st.floats(1.0, 1.5),
    seed=st.integers(0, 2**31 - 1),
)
def test_throttle_jax_matches_numpy(n, thr, seed):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.0, 3.0, size=(2, n))
    wo = rng.uniform(0.0, 3.0, size=(2, n))
    wo[0, 0] = 0.0  # the perf_without == 0 guard branch
    ref = throttle_decision(w, wo, thr)
    jx = _throttle_jax(w, wo, thr)
    assert jx.dtype == bool
    np.testing.assert_array_equal(jx, ref)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_throttle_jax_batched_threshold_rows(seed):
    """(P, 1) per-row speedup thresholds — the param_grid batching form."""
    rng = np.random.default_rng(seed)
    P, n = 4, 8
    w = rng.uniform(0.5, 2.0, size=(P, n))
    wo = rng.uniform(0.5, 2.0, size=(P, n))
    thr = rng.uniform(1.0, 1.3, size=(P, 1))
    ref = throttle_decision(w, wo, thr)
    np.testing.assert_array_equal(_throttle_jax(w, wo, thr), ref)
