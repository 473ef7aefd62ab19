"""Batched JAX Lookahead allocator vs the numpy golden reference.

Contract (see ``src/repro/core/cache_controller_jax.py``): bit-identical
allocations away from tie knife-edges, under the documented deterministic
tie-breaks (lowest client index wins equal marginal utility; smallest step
wins within a client; the zero-utility spread orders by remaining gain with
a stable sort).  Random float curves make exact mu ties measure-zero, so
these tests assert exact equality.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    CacheController,
    allocator_calls,
    cppf_allocate,
    lookahead_allocate,
)
from repro.core import cache_controller_jax as ccj
from repro.core.x64 import x64_context


def _concave_curves(rng, n, total):
    u = np.arange(total + 1, dtype=np.float64)
    scales = rng.uniform(0.0, 50.0, size=n)
    rates = rng.uniform(2.0, 40.0, size=n)
    return scales[:, None] * (1.0 - np.exp(-u[None, :] / rates[:, None]))


def _nonmonotone_curves(rng, n, total):
    return np.cumsum(rng.normal(0.0, 1.0, size=(n, total + 1)), axis=1)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_batched_matches_reference_on_monotone_curves(n, total, seed):
    rng = np.random.default_rng(seed)
    curves = _concave_curves(rng, n, total)
    min_units = int(rng.integers(0, max(total // n, 1)))
    ref = lookahead_allocate(curves, total, min_units)
    got = ccj.lookahead_allocate(curves, total, min_units)
    np.testing.assert_array_equal(ref, got)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_batched_matches_reference_on_nonmonotone_curves(n, total, seed):
    """Non-monotone curves exercise negative marginal utilities and the
    spread-remainder branch (max mu <= 0 mid-distribution)."""
    rng = np.random.default_rng(seed)
    curves = _nonmonotone_curves(rng, n, total)
    min_units = int(rng.integers(0, max(total // n, 1)))
    ref = lookahead_allocate(curves, total, min_units)
    got = ccj.lookahead_allocate(curves, total, min_units)
    np.testing.assert_array_equal(ref, got)


def test_spread_remainder_branch_flat_curves():
    """Zero utility everywhere: the even-spread branch fires immediately
    and both backends distribute the whole balance the same way."""
    total = 37
    for n in (2, 3, 5):
        curves = np.zeros((n, total + 1))
        ref = lookahead_allocate(curves, total, min_units=2)
        got = ccj.lookahead_allocate(curves, total, min_units=2)
        np.testing.assert_array_equal(ref, got)
        assert got.sum() == total


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), min_units=st.integers(1, 5))
def test_batched_respects_min_units_floor(seed, min_units):
    rng = np.random.default_rng(seed)
    n, total = 6, 64
    curves = _nonmonotone_curves(rng, n, total)
    got = ccj.lookahead_allocate(curves, total, min_units)
    assert (got >= min_units).all()
    assert got.sum() == total
    np.testing.assert_array_equal(
        got, lookahead_allocate(curves, total, min_units))


def test_batched_leading_axes_and_per_batch_min_units():
    rng = np.random.default_rng(3)
    n, total = 5, 40
    curves = np.stack([
        np.stack([_concave_curves(rng, n, total) for _ in range(3)])
        for _ in range(2)])                        # (2, 3, n, U+1)
    mins = np.array([[1, 2, 3], [4, 0, 2]])        # broadcast per element
    got = ccj.lookahead_allocate(curves, total, mins)
    assert got.shape == (2, 3, n)
    for i in range(2):
        for j in range(3):
            np.testing.assert_array_equal(
                got[i, j],
                lookahead_allocate(curves[i, j], total, int(mins[i, j])))


def test_batched_rejects_infeasible_inputs():
    with pytest.raises(ValueError):
        ccj.lookahead_allocate(np.zeros((4, 9)), 8, min_units=4)
    with pytest.raises(ValueError):
        ccj.lookahead_allocate(np.zeros((4, 12)), 8, min_units=4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_masked_matches_cppf_reference(n, total, seed):
    """The CPpf friendly-mask variant matches the scalar subset call,
    including the min_units pinning of inactive clients."""
    rng = np.random.default_rng(seed)
    curves = np.cumsum(
        np.abs(rng.normal(0.0, 1.0, size=(n, total + 1))), axis=1)
    min_units = int(rng.integers(1, max(total // n, 2)))
    active = rng.integers(0, 2, size=n).astype(bool)
    ref = cppf_allocate(curves, total, min_units, active)
    got = ccj.lookahead_allocate_masked(curves, total, min_units, active)
    np.testing.assert_array_equal(ref, got)
    assert got.sum() == total
    if active.any():   # otherwise the even-split exceeds the floor
        assert (got[~active] == min_units).all()


def test_masked_all_inactive_distributes_remainder():
    """All-friendly CPpf mixes: capacity splits evenly and the remainder
    goes to the lowest-index clients — no unit is dropped (the former
    floor-division bug)."""
    total, n, min_units = 30, 4, 4
    curves = np.zeros((n, total + 1))
    ref = cppf_allocate(curves, total, min_units, np.zeros(n, dtype=bool))
    got = ccj.lookahead_allocate_masked(
        curves, total, min_units, np.zeros(n, dtype=bool))
    np.testing.assert_array_equal(ref, got)
    assert ref.sum() == total          # 30 = 8 + 8 + 7 + 7
    np.testing.assert_array_equal(ref, [8, 8, 7, 7])


def test_lookahead_runs_with_x64_off_globally():
    """The batched allocator enters its own float64 scope: it needs no
    process-wide ``jax_enable_x64`` and matches the golden without it."""
    import jax

    assert not jax.config.jax_enable_x64
    rng = np.random.default_rng(17)
    curves = np.stack([_concave_curves(rng, 6, 48) for _ in range(3)])
    out = ccj.lookahead_allocate(curves, 48, min_units=2)
    ref = np.stack([lookahead_allocate(c, 48, min_units=2) for c in curves])
    np.testing.assert_array_equal(out, ref)
    assert not jax.config.jax_enable_x64


def test_cache_controller_backend_dispatch():
    """Both backends agree through the CacheController facade, and only
    the numpy backend touches the host allocator counter."""
    rng = np.random.default_rng(11)
    n, total = 6, 48
    batch = np.stack([_nonmonotone_curves(rng, n, total) for _ in range(4)])
    ctl_np = CacheController(total, min_units=2, backend="numpy")
    ctl_jx = CacheController(total, min_units=2, backend="jax")

    before = allocator_calls()
    out_np = ctl_np.allocate(batch)
    assert allocator_calls() - before == 4      # one host call per element

    before = allocator_calls()
    out_jx = ctl_jx.allocate(batch)
    assert allocator_calls() - before == 0      # device-resident
    np.testing.assert_array_equal(out_np, out_jx)

    active = rng.integers(0, 2, size=(4, n)).astype(bool)
    np.testing.assert_array_equal(
        ctl_np.allocate_masked(batch, active),
        ctl_jx.allocate_masked(batch, active))

    # "pallas" is a valid backend since the lookahead_greedy kernel landed
    # (tests/test_lookahead_kernel.py); anything else still rejects.
    with pytest.raises(ValueError):
        CacheController(total, backend="mosaic")


def _adversarial_refresh_curves(n, U):
    """Worst case for the greedy's trip count: client 0 is concave (best
    step 1, highest mu early — many one-unit steps), every other client
    convex with near-tied shapes, so the best step and its owner keep
    shifting as the balance cap shrinks.  Under the one-stale-client
    incremental refresh this maximizes cache invalidations between
    greedy steps — each step dirties the winner AND shrinks every other
    client's cap, forcing refresh trips before the next step."""
    u = np.arange(U + 1, dtype=np.float64)
    curves = np.empty((n, U + 1))
    curves[0] = 100.0 * (1.0 - np.exp(-u / 3.0))
    for i in range(1, n):
        curves[i] = (u / U) ** 2 * (80.0 - 0.5 * i)
    return curves


def _clamp_curves(n, U):
    """Client 0 linear, every other client flat: the greedy hands client 0
    every unit above the floors, one at a time, so its allocation reaches
    the top column, where the curve shift clamps at the edge."""
    curves = np.zeros((n, U + 1))
    curves[0] = np.arange(U + 1, dtype=np.float64)
    return curves


def _greedy_case(name):
    """``(curves, min_units, active, U)`` of one trajectory case."""
    rng = np.random.default_rng(5)
    if name == "adversarial":
        n, U = 8, 96
        curves = np.stack([
            _adversarial_refresh_curves(n, U),
            _nonmonotone_curves(np.random.default_rng(0), n, U),
            np.zeros((n, U + 1)),
            _concave_curves(np.random.default_rng(1), n, U),
        ])
        return curves, np.array([0, 3, 2, 1]), np.ones((4, n), bool), U
    if name == "min_units_0":
        n, U = 6, 64
        curves = np.stack([_concave_curves(rng, n, U) for _ in range(3)])
        return curves, np.zeros(3, int), np.ones((3, n), bool), U
    if name == "clamp_top":
        n, U = 5, 40
        curves = np.stack([_clamp_curves(n, U), _concave_curves(rng, n, U)])
        return curves, np.array([0, 2]), np.ones((2, n), bool), U
    if name == "u96_nonmonotone":
        n, U = 5, 96        # U + 1 = 97 columns: no power of two
        curves = np.stack([_nonmonotone_curves(rng, n, U) for _ in range(2)])
        return curves, np.array([1, 4]), np.ones((2, n), bool), U
    if name == "inactive_row":
        n, U = 6, 48
        curves = np.stack([np.cumsum(np.abs(rng.normal(size=(n, U + 1))), 1)
                           for _ in range(2)])
        active = np.array([[True, False, True, True, False, True],
                           [False] * n])
        return curves, np.array([3, 2]), active, U
    if name == "single_row":
        n, U = 16, 256      # the sweep's client count and capacity
        return (_concave_curves(rng, n, U)[None], np.array([4]),
                np.ones((1, n), bool), U)
    raise KeyError(name)


@pytest.mark.parametrize("case, bodies", [
    ("adversarial", 92),
    ("min_units_0", 64),
    ("clamp_top", 40),
    ("u96_nonmonotone", 24),
    ("inactive_row", 20),
    ("single_row", 192),
])
def test_greedy_loop_trip_bound_never_abandons_live_rows(case, bodies):
    """Audit of the ``_greedy_loop`` trip bound and its trajectory.  The
    incremental-refresh loop runs under an ``(n + 2) * U`` bound, which
    is safe: the greedy takes <= U unit-consuming steps per row, and
    between consecutive steps each of the n clients refreshes at most
    once (a refreshed entry stays valid until the next step dirties the
    winner or shrinks the cap below its k), so body applications are
    bounded by n * U + 1 < (n + 2) * U.  The adversarial curve family
    maximizes invalidations between steps; the loop must still exit with
    every row finished (balance drained or stuck), never via the bound —
    abandoning a live row would silently hand a short allocation to the
    zero-spread tail.  The pinned body counts are those of the gathering
    loop the gather-free one replaced: the same count is the same
    trajectory.  The other cases hit the curve shift's edges: a zero
    floor, an allocation at the top column (the clamp), a curve width
    that is no power of two, a row with no active client, one row."""
    import jax.numpy as jnp

    curves, mins, active, U = _greedy_case(case)
    B, n, _ = curves.shape
    remaining = U - mins * (n - active.sum(axis=-1))
    with x64_context():
        alloc, balance, stuck, it = map(np.asarray, ccj._greedy_loop(
            jnp.asarray(curves, jnp.float64), jnp.asarray(mins),
            jnp.asarray(active), jnp.asarray(remaining, jnp.int32),
            total_units=U))
        # The shift the loop reads its gains through equals the clamped
        # gather at the allocation it ended on.
        shifted = np.asarray(ccj._shift_clamped(
            jnp.asarray(curves, jnp.float64), jnp.asarray(alloc)))
    cols = np.minimum(alloc[..., None] + np.arange(U + 1), U)
    np.testing.assert_array_equal(
        shifted, np.take_along_axis(curves, cols, axis=-1))
    assert int(it) == bodies
    # The loop retired every row on its own terms, not via the bound.
    assert int(it) < (n + 2) * U
    assert np.all((balance == 0) | stuck)
    assert np.all(balance >= 0)
    # And the full pipeline (greedy + spread) still matches the golden.
    if active.all():
        got = ccj.lookahead_allocate(curves, U, mins)
        want = [lookahead_allocate(curves[b], U, int(mins[b]))
                for b in range(B)]
    else:
        got = ccj.lookahead_allocate_masked(curves, U, mins, active)
        want = [cppf_allocate(curves[b], U, int(mins[b]), active[b])
                for b in range(B)]
    for b in range(B):
        np.testing.assert_array_equal(got[b], want[b])


def test_greedy_loop_lowers_without_gathers():
    """At the sweep's shape (56 rows, 16 clients, 256 units, float64) the
    greedy's lowered program holds no gather and no dynamic slice: a TPU
    reads those element by element, so a gathering body's cost grew with
    its rows.  Guarded here on every platform, without a chip."""
    import jax
    import jax.numpy as jnp

    B, n, U = 56, 16, 256
    with x64_context():
        text = ccj._greedy_loop.lower(
            jax.ShapeDtypeStruct((B, n, U + 1), jnp.float64),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, n), jnp.bool_),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            total_units=U).as_text()
    assert "stablehlo.while" in text
    assert "stablehlo.gather" not in text
    assert "stablehlo.dynamic_slice" not in text
