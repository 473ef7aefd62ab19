"""Unit + property tests for the CBP controllers (paper §3.2)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    SampledATD,
    StackDistanceMonitor,
    allocate_bandwidth,
    lookahead_allocate,
    throttle_decision,
)

# --------------------------------------------------------------------- #
# Lookahead / UCP (paper §3.2.1)
# --------------------------------------------------------------------- #


def _concave_curve(total, scale, rate):
    u = np.arange(total + 1, dtype=np.float64)
    return scale * (1.0 - np.exp(-u / rate))


def test_lookahead_prefers_high_utility_client():
    total = 64
    curves = np.stack([
        _concave_curve(total, scale=100.0, rate=8.0),   # cache-hungry
        _concave_curve(total, scale=1.0, rate=8.0),     # insensitive
    ])
    alloc = lookahead_allocate(curves, total, min_units=4)
    assert alloc.sum() == total
    assert alloc[0] > alloc[1]
    assert alloc[1] >= 4


def test_lookahead_flat_curves_split_evenly_ish():
    total = 64
    curves = np.zeros((4, total + 1))
    alloc = lookahead_allocate(curves, total, min_units=4)
    assert alloc.sum() == total
    assert alloc.min() >= 4


def test_lookahead_respects_min_units():
    total = 32
    curves = np.stack([
        _concave_curve(total, 100.0, 4.0),
        np.zeros(total + 1),
    ])
    alloc = lookahead_allocate(curves, total, min_units=6)
    assert alloc[1] >= 6
    assert alloc.sum() == total


def test_lookahead_rejects_infeasible_min():
    with pytest.raises(ValueError):
        lookahead_allocate(np.zeros((4, 9)), 8, min_units=4)


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(2, 6),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_lookahead_properties(n, total, seed):
    """Capacity is always fully distributed; floors always respected."""
    rng = np.random.default_rng(seed)
    scales = rng.uniform(0.0, 50.0, size=n)
    rates = rng.uniform(2.0, 40.0, size=n)
    u = np.arange(total + 1, dtype=np.float64)
    curves = scales[:, None] * (1.0 - np.exp(-u[None, :] / rates[:, None]))
    alloc = lookahead_allocate(curves, total, min_units=2)
    assert int(alloc.sum()) == total
    assert (alloc >= 2).all()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_lookahead_monotone_in_utility(seed):
    """A strictly more cache-hungry client never gets less cache."""
    total = 64
    rng = np.random.default_rng(seed)
    base = _concave_curve(total, rng.uniform(5, 20), rng.uniform(4, 30))
    hungry = 3.0 * base
    other = _concave_curve(total, rng.uniform(5, 20), rng.uniform(4, 30))
    a1 = lookahead_allocate(np.stack([base, other]), total, 4)
    a2 = lookahead_allocate(np.stack([hungry, other]), total, 4)
    assert a2[0] >= a1[0]


# --------------------------------------------------------------------- #
# Bandwidth controller / Algorithm 1 (paper §3.2.2)
# --------------------------------------------------------------------- #


def test_bandwidth_proportional_to_delay():
    alloc = allocate_bandwidth(np.array([3.0, 1.0]), 16.0, 1.0)
    # floors: 1 each; remaining 14 split 3:1
    np.testing.assert_allclose(alloc, [1 + 10.5, 1 + 3.5])


def test_bandwidth_zero_delay_even_split():
    alloc = allocate_bandwidth(np.zeros(4), 64.0, 1.0)
    np.testing.assert_allclose(alloc, np.full(4, 16.0))


def test_bandwidth_infeasible_floor():
    with pytest.raises(ValueError):
        allocate_bandwidth(np.ones(8), 4.0, 1.0)


@settings(max_examples=50, deadline=None)
@given(
    n=st.integers(1, 16),
    total=st.floats(16.0, 128.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_bandwidth_properties(n, total, seed):
    """Sums to total; floor respected; monotone in delay."""
    rng = np.random.default_rng(seed)
    delay = rng.uniform(0.0, 100.0, size=n)
    alloc = allocate_bandwidth(delay, total, min_allocation=0.5)
    assert np.isclose(alloc.sum(), total)
    assert (alloc >= 0.5 - 1e-9).all()
    order = np.argsort(delay)
    assert (np.diff(alloc[order]) >= -1e-9).all()


# --------------------------------------------------------------------- #
# Prefetch throttle / Algorithm 2 (paper §3.2.3)
# --------------------------------------------------------------------- #


def test_throttle_threshold():
    on = throttle_decision(
        np.array([1.10, 1.04, 0.90]), np.array([1.0, 1.0, 1.0]),
        speedup_threshold=1.05)
    assert on.tolist() == [True, False, False]


@settings(max_examples=50, deadline=None)
@given(
    ipc=st.floats(0.01, 10.0),
    speedup=st.floats(0.1, 3.0),
    thr=st.floats(1.0, 1.5),
)
def test_throttle_property(ipc, speedup, thr):
    from hypothesis import assume
    assume(abs(speedup - thr) > 1e-6)  # avoid the float knife-edge
    on = throttle_decision(
        np.array([ipc * speedup]), np.array([ipc]), speedup_threshold=thr)
    assert bool(on[0]) == (speedup > thr)


# --------------------------------------------------------------------- #
# ATD / stack-distance monitor (paper §3.4)
# --------------------------------------------------------------------- #


def test_sampled_atd_halving():
    atd = SampledATD(2, 8)
    atd.record(np.ones((2, 9)))
    atd.halve()
    np.testing.assert_allclose(atd.utility_curves(), 0.5)


def test_stack_distance_monitor_lru():
    mon = StackDistanceMonitor(max_units=4)
    for k in "abcd":
        mon.access(k)          # cold misses
    assert mon.access("d") == 0   # MRU
    assert mon.access("a") == 3   # LRU depth
    curve = mon.utility_curve()
    assert curve[0] == 0
    assert (np.diff(curve) >= 0).all()


@settings(max_examples=25, deadline=None)
@given(
    keys=st.lists(st.integers(0, 10), min_size=1, max_size=200),
    cap=st.integers(2, 12),
)
def test_stack_distance_curve_counts_hits(keys, cap):
    """With cap units, hits(cap) == number of accesses at distance < cap."""
    mon = StackDistanceMonitor(max_units=cap)
    hits_direct = 0
    for k in keys:
        d = mon.access(k)
        if d < cap:
            hits_direct += 1
    assert mon.utility_curve()[cap] == pytest.approx(hits_direct)
    # non-decreasing
    assert (np.diff(mon.utility_curve()) >= 0).all()
