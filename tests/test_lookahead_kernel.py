"""The Lookahead greedy Pallas kernel vs the numpy golden reference.

Contract (see ``src/repro/kernels/lookahead_greedy``): the interpret-mode
kernel, its numpy ``ref.py`` oracle and the batched while_loop backend are
ALL bit-identical to the golden
(:func:`repro.core.cache_controller.lookahead_allocate` /
:func:`~repro.core.cache_controller.cppf_allocate`) away from tie
knife-edges — the kernel swaps only *how* the greedy while-loop executes,
never a tie-break or a rounding.  Random float curves make exact mu ties
measure-zero, so these tests assert exact equality.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CacheController, allocator_calls
from repro.core import cache_controller as cc
from repro.core import cache_controller_jax as ccj
from repro.kernels.lookahead_greedy.ref import (
    lookahead_masked_ref,
    lookahead_ref,
)

pytestmark = pytest.mark.slow


def _curves(rng, n, total, kind):
    if kind == "concave":
        u = np.arange(total + 1, dtype=np.float64)
        return (rng.uniform(0.0, 50.0, n)[:, None]
                * (1.0 - np.exp(-u[None, :]
                                / rng.uniform(2.0, 40.0, n)[:, None])))
    if kind == "nonmonotone":
        return np.cumsum(rng.normal(0.0, 1.0, (n, total + 1)), axis=1)
    return np.zeros((n, total + 1))


# ------------------------------ ref.py ----------------------------- #


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_ref_matches_golden(n, total, seed):
    """The kernel's numpy oracle is pinned to the repo golden."""
    rng = np.random.default_rng(seed)
    for kind in ("concave", "nonmonotone", "flat"):
        curves = _curves(rng, n, total, kind)
        min_units = int(rng.integers(0, max(total // n, 1)))
        np.testing.assert_array_equal(
            lookahead_ref(curves, total, min_units),
            cc.lookahead_allocate(curves, total, min_units),
            err_msg=kind)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_masked_ref_matches_cppf_golden(n, total, seed):
    rng = np.random.default_rng(seed)
    curves = np.cumsum(
        np.abs(rng.normal(0.0, 1.0, (n, total + 1))), axis=1)
    min_units = int(rng.integers(1, max(total // n, 2)))
    active = rng.integers(0, 2, n).astype(bool)
    np.testing.assert_array_equal(
        lookahead_masked_ref(curves, total, min_units, active),
        cc.cppf_allocate(curves, total, min_units, active))


def test_masked_ref_all_inactive_even_split():
    got = lookahead_masked_ref(
        np.zeros((4, 31)), 30, 4, np.zeros(4, dtype=bool))
    np.testing.assert_array_equal(got, [8, 8, 7, 7])


# ----------------------- kernel (interpret mode) -------------------- #


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_kernel_bit_identical_to_golden(n, total, seed):
    """The whole batch through the Pallas backend equals the golden
    element by element — concave, non-monotone and flat curves."""
    rng = np.random.default_rng(seed)
    for kind in ("concave", "nonmonotone", "flat"):
        curves = np.stack(
            [_curves(rng, n, total, kind) for _ in range(3)])
        min_units = int(rng.integers(0, max(total // n, 1)))
        got = ccj.lookahead_allocate(
            curves, total, min_units, backend="pallas")
        for b in range(3):
            np.testing.assert_array_equal(
                got[b], cc.lookahead_allocate(curves[b], total, min_units),
                err_msg=kind)


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(2, 8),
    total=st.integers(24, 96),
    seed=st.integers(0, 2**31 - 1),
)
def test_kernel_masked_bit_identical_to_cppf_golden(n, total, seed):
    """The masked CPpf variant through the Pallas backend, incl. pinned
    inactive clients and the all-inactive even-split fallback."""
    rng = np.random.default_rng(seed)
    curves = np.cumsum(
        np.abs(rng.normal(0.0, 1.0, (n, total + 1))), axis=1)
    min_units = int(rng.integers(1, max(total // n, 2)))
    for active in (rng.integers(0, 2, n).astype(bool),
                   np.ones(n, dtype=bool),
                   np.zeros(n, dtype=bool)):
        got = ccj.lookahead_allocate_masked(
            curves, total, min_units, active, backend="pallas")
        np.testing.assert_array_equal(
            got, cc.cppf_allocate(curves, total, min_units, active))
        assert got.sum() == total


def test_kernel_agrees_with_while_loop_backend():
    """Both device backends produce the same bits through the same
    zero-utility spread."""
    rng = np.random.default_rng(7)
    n, total = 6, 64
    batch = np.stack(
        [_curves(rng, n, total, "nonmonotone") for _ in range(5)])
    mins = np.array([0, 1, 2, 3, 4])
    np.testing.assert_array_equal(
        ccj.lookahead_allocate(batch, total, mins, backend="pallas"),
        ccj.lookahead_allocate(batch, total, mins, backend="jax"))


def test_cache_controller_pallas_backend_device_resident():
    """The facade's pallas backend matches numpy bit for bit and never
    touches the host allocator counter."""
    rng = np.random.default_rng(11)
    n, total = 6, 48
    batch = np.stack(
        [_curves(rng, n, total, "nonmonotone") for _ in range(4)])
    ctl_np = CacheController(total, min_units=2, backend="numpy")
    ctl_pl = CacheController(total, min_units=2, backend="pallas")
    before = allocator_calls()
    np.testing.assert_array_equal(
        ctl_np.allocate(batch), ctl_pl.allocate(batch))
    active = rng.integers(0, 2, size=(4, n)).astype(bool)
    np.testing.assert_array_equal(
        ctl_np.allocate_masked(batch, active),
        ctl_pl.allocate_masked(batch, active))
    # numpy side incremented the counter; the pallas side added nothing.
    assert allocator_calls() - before == 8


def test_unknown_lookahead_backend_rejected():
    with pytest.raises(ValueError):
        ccj.lookahead_allocate(np.zeros((2, 4, 9)), 8, 0, backend="mosaic")
