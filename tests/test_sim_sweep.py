"""Batched JAX sweep substrate vs the numpy golden reference.

Contract (see ``src/repro/sim/memsys_jax.py``): the jitted JAX interval
model must match ``memsys`` to 1e-5 relative tolerance, and ``run_sweep``
must reproduce the scalar manager results without ever calling the scalar
``memsys.evaluate`` per (mix, manager) pair.
"""
import numpy as np
import pytest

from repro.core import CBPParams, allocator_calls
from repro.sim import (
    MANAGER_NAMES,
    WORKLOADS,
    baseline_ipc,
    memsys,
    random_mixes,
    run_all_managers,
    run_sweep,
    stack,
    weighted_speedup,
)
from repro.sim import memsys_jax

FIELDS = ("ipc", "queuing_delay_ns", "traffic_gbps", "mpki",
          "exposed_mpki", "occupancy_units")


def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / (np.abs(a) + 1e-12)))


@pytest.mark.parametrize("cache_partitioned", [True, False])
@pytest.mark.parametrize("bandwidth_partitioned", [True, False])
def test_memsys_jax_matches_numpy_reference(cache_partitioned,
                                            bandwidth_partitioned):
    """Randomized (mix, allocation) batches, every partitioning regime."""
    rng = np.random.default_rng(42)
    for mix in [WORKLOADS["w1"][:8], random_mixes(1, 8, seed=5)[0]]:
        apps = stack(mix)
        n = apps.n
        cu = rng.uniform(4.0, 40.0, size=(6, n))
        bw = rng.uniform(1.0, 8.0, size=(6, n))
        pf = rng.integers(0, 2, size=(6, n)).astype(np.float64)
        kwargs = dict(
            cache_partitioned=cache_partitioned,
            bandwidth_partitioned=bandwidth_partitioned,
            total_cache_units=16.0 * n,
            total_bandwidth_gbps=4.0 * n,
        )
        ref = memsys.evaluate(apps, cu, bw, pf, **kwargs)
        jx = memsys_jax.evaluate(apps, cu, bw, pf, **kwargs)
        for field in FIELDS:
            err = _rel_err(getattr(ref, field), getattr(jx, field))
            assert err < 1e-5, (field, err)


def test_interval_model_runs_in_float64_with_x64_off_globally():
    """The x64 scope is real: with ``jax_enable_x64`` off process-wide, the
    interval model still solves and returns float64 (a silent float32
    fallback is what broke the 1e-5 parity contract once)."""
    import jax

    assert not jax.config.jax_enable_x64
    apps = stack(WORKLOADS["w1"][:4])
    out = memsys_jax.evaluate(apps, np.full(4, 16.0), np.full(4, 4.0),
                              np.zeros(4), total_cache_units=64.0,
                              total_bandwidth_gbps=16.0)
    for field in FIELDS:
        assert getattr(out, field).dtype == np.float64, field
    assert not jax.config.jax_enable_x64


def test_utility_curves_jax_matches_numpy_reference():
    rng = np.random.default_rng(7)
    apps = stack(WORKLOADS["w3"])
    n = apps.n
    pf = rng.integers(0, 2, size=n).astype(np.float64)
    ipc = rng.uniform(0.2, 2.0, size=n)
    ref = memsys.utility_curves(apps, pf, ipc, 64, duration_ms=1.0)
    jx = memsys_jax.utility_curves(apps, pf, ipc, 64, duration_ms=1.0)
    assert _rel_err(ref, np.asarray(jx)) < 1e-5


def test_sweep_matches_scalar_manager_path():
    """One-mix sweep == run_all_managers on the numpy reference plant.

    The batched coordinator shares the Fig. 8 schedule and controller state
    with the scalar path, so the only divergence source is the 1e-5 model
    parity gap (controller decisions are integer/boolean and identical away
    from knife-edges)."""
    mix = WORKLOADS["w1"]
    res = run_sweep([mix], total_ms=40.0)
    scalar = run_all_managers(mix, total_ms=40.0)
    base = baseline_ipc(mix)
    assert _rel_err(res.baseline_ipc[0], base) < 1e-5
    for name in MANAGER_NAMES:
        ws_batched = float(res.weighted_speedup(name)[0])
        ws_scalar = weighted_speedup(scalar[name].ipc, base)
        assert ws_batched == pytest.approx(ws_scalar, rel=1e-4), name


def test_sweep_8x10_without_scalar_evaluate(monkeypatch):
    """8 mixes x 10 managers completes with the scalar model forbidden."""
    def _forbidden(*args, **kwargs):
        raise AssertionError(
            "run_sweep must not fall back to per-pair memsys.evaluate")
    monkeypatch.setattr(memsys, "evaluate", _forbidden)
    monkeypatch.setattr(memsys, "utility_curves", _forbidden)

    mixes = random_mixes(8, 16, seed=11)
    res = run_sweep(mixes, total_ms=20.0)
    assert res.n_mixes == 8
    assert set(res.ipc) == set(MANAGER_NAMES)
    for name in MANAGER_NAMES:
        assert res.ipc[name].shape == (8, 16)
        assert np.isfinite(res.ipc[name]).all()
        assert (res.ipc[name] > 0).all()
    # Allocation invariants per mix (as in the scalar manager tests).
    cbp = res.final_alloc["CBP"]
    assert (cbp.cache_units.sum(axis=-1) == 256).all()
    assert (cbp.cache_units >= 4).all()
    np.testing.assert_allclose(cbp.bandwidth.sum(axis=-1), 64.0)


def test_sweep_preserves_cbp_beats_baseline_ordering():
    """The ordering asserted in tests/test_sim_managers.py survives the
    batched path: CBP geomean beats every single-resource manager."""
    mixes = [WORKLOADS["w1"], WORKLOADS["w2"]] + random_mixes(2, 16, seed=3)
    names = ["equal off", "only cache", "only bw", "only pref", "CBP"]
    res = run_sweep(mixes, managers=names, total_ms=40.0)
    cbp = res.geomean_speedup("CBP")
    assert cbp > 1.10
    for single in ("only cache", "only bw", "only pref", "equal off"):
        assert cbp > res.geomean_speedup(single), single
    assert (res.weighted_speedup("CBP") > 1.0).all()


def test_sweep_performs_zero_host_allocator_calls():
    """Device-resident contract: the batched sweep never calls the numpy
    ``lookahead_allocate`` per mix — reconfigurations run as batched JAX
    device calls (repro.core.cache_controller_jax)."""
    mixes = random_mixes(3, 16, seed=9)
    before = allocator_calls()
    res = run_sweep(mixes, managers=["only cache", "CPpf", "CBP"],
                    total_ms=20.0)
    assert allocator_calls() == before
    for name in ("only cache", "CPpf", "CBP"):
        assert (res.final_alloc[name].cache_units.sum(axis=-1) == 256).all()
        assert (res.final_alloc[name].cache_units >= 4).all()


def test_sweep_param_grid_batches_design_space():
    """`param_grid` adds a leading CBPParams axis; same-schedule params run
    as one device batch and every slice matches an independent sweep."""
    grid = [CBPParams(min_bandwidth_allocation=0.5),
            CBPParams(min_bandwidth_allocation=1.0),     # same schedule
            CBPParams(reconfiguration_interval_ms=5.0)]  # distinct schedule
    mixes = [WORKLOADS["w1"], WORKLOADS["w2"]]
    # "equal on" is CBPParams-independent: evaluated once, broadcast to P.
    names = ["equal on", "CBP", "CPpf"]
    res = run_sweep(mixes, managers=names, total_ms=20.0, param_grid=grid)
    assert res.param_grid == grid
    assert res.ipc["CBP"].shape == (3, 2, 16)
    assert res.weighted_speedup("CBP").shape == (3, 2)
    assert np.shape(res.geomean_speedup("CBP")) == (3,)
    for name in names:
        assert res.ipc[name].shape == (3, 2, 16)
        assert (res.final_alloc[name].cache_units.sum(axis=-1) == 256).all()
    for pi, p in enumerate(grid):
        ref = run_sweep(mixes, managers=names, total_ms=20.0, params=p)
        for name in names:
            np.testing.assert_array_equal(res.ipc[name][pi], ref.ipc[name])
    with pytest.raises(ValueError):
        run_sweep(mixes, managers=["CBP"], params=CBPParams(),
                  param_grid=grid)


def test_random_mixes_shapes_and_balance():
    mixes = random_mixes(5, 16, seed=0)
    assert len(mixes) == 5
    assert all(len(m) == 16 for m in mixes)
    from repro.sim.workloads import _CLASS_BUCKETS
    for mix in mixes:
        for bucket in _CLASS_BUCKETS.values():
            assert any(a in bucket for a in mix)
    # deterministic in the seed
    assert mixes == random_mixes(5, 16, seed=0)
    assert mixes != random_mixes(5, 16, seed=1)
