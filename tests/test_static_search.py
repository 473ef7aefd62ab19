"""The batched Fig. 5 static search (PR 4): parity, properties, smoke.

Contracts under test (see ``src/repro/sim/static_search.py``):

* the JAX backend matches the numpy references — both the
  ``search_static(backend="numpy")`` golden path and the independent
  ``benchmarks.paper_figs._exhaustive_best`` implementation — within
  1e-5 relative weighted speedup, with the SAME argmax/top-k config
  indices under the documented lowest-enumeration-index tie-break;
* a full search is AT MOST TWO device programs — every family's chunked
  grid scan stacked inside ONE program plus the shared baseline
  evaluation (dispatch counter), bit-identical per family to the
  one-program-per-family path (``stack_families=False``);
* enumerated grids are sum-feasible, padding masks never let a
  masked/infeasible config win, and top-k results are sorted descending
  with distinct indices;
* the workload axis shards across forced host devices with identical
  results;
* the Fig. 5 baseline construction is the shared
  :func:`repro.sim.equal_share` helper (``equal_on`` geomean pinned);
* the ``fig5_potential`` benchmark entry point reproduces the paper's
  ordering (all-three >= best two-resource subset).
"""
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.paper_figs import _exhaustive_best
from repro.core import device_dispatches, reset_device_dispatches
from repro.sim import equal_share
from repro.sim.static_search import (
    FIG5_FAMILIES,
    FIG5_TWO_RESOURCE,
    FamilySpec,
    InfeasibleGridError,
    StaticOptions,
    enumerate_grid,
    family_grid,
    registry_families,
    search_static,
)
from repro.sim.workloads import random_workloads
from hypothesis import given, settings
from hypothesis import strategies as st

# --------------------------------------------------------------------- #
# parity
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("n_apps,seed", [(2, 3), (3, 5)])
def test_batched_matches_numpy_backend(n_apps, seed):
    """JAX vs numpy backend: 1e-5 weighted speedup, identical top-k
    config indices (documented tie-break: lowest enumeration index)."""
    wls = random_workloads(4, n_apps, seed=seed)
    jx = search_static(wls, k=3, backend="jax")
    ref = search_static(wls, k=3, backend="numpy")
    assert jx.family_names == ref.family_names
    for fam in jx.family_names:
        np.testing.assert_allclose(jx.topk_ws[fam], ref.topk_ws[fam],
                                   rtol=1e-5, err_msg=fam)
        np.testing.assert_array_equal(jx.topk_index[fam],
                                      ref.topk_index[fam], err_msg=fam)
    np.testing.assert_allclose(jx.baseline_ipc, ref.baseline_ipc,
                               rtol=1e-5)


@pytest.mark.parametrize("n_apps,seed", [(2, 3), (3, 5)])
def test_batched_matches_exhaustive_best_reference(n_apps, seed):
    """The independent benchmarks-side numpy implementation pins the
    best weighted speedup of every (workload, family)."""
    wls = random_workloads(3, n_apps, seed=seed)
    res = search_static(wls)
    for fam, spec in FIG5_FAMILIES.items():
        for wi, w in enumerate(wls):
            ref = _exhaustive_best(w, spec.manage_cache, spec.manage_bw,
                                   spec.manage_pf, spec.pf_all_on)
            assert res.best_ws(fam)[wi] == pytest.approx(ref, rel=1e-5), \
                (fam, wi)


def test_stacked_search_is_two_device_programs():
    """The stacked dispatch contract: ONE program scanning every family
    back to back plus one shared baseline evaluation — nothing per
    family, workload or config."""
    wls = random_workloads(3, 3, seed=1)
    reset_device_dispatches()
    res = search_static(wls, k=2)
    assert device_dispatches() == 2
    for fam in res.family_names:
        assert np.isfinite(res.best_ws(fam)).all()


def test_per_family_path_dispatches_one_program_per_family():
    """The stacking parity reference keeps the PR 4 shape: len(families)
    search programs plus the shared baseline evaluation."""
    wls = random_workloads(3, 3, seed=1)
    reset_device_dispatches()
    search_static(wls, k=2, stack_families=False)
    assert device_dispatches() == len(FIG5_FAMILIES) + 1


@pytest.mark.parametrize("n_apps,k,seed", [(2, 1, 3), (3, 4, 5)])
def test_stacked_bit_identical_to_per_family_path(n_apps, k, seed):
    """THE family-stacking property: batching the family axis changes
    nothing — every family's top-k weighted speedups and config indices
    out of the stacked program equal the per-family programs bit for
    bit."""
    wls = random_workloads(4, n_apps, seed=seed)
    st = search_static(wls, k=k)
    pf = search_static(wls, k=k, stack_families=False)
    assert st.family_names == pf.family_names
    for fam in st.family_names:
        np.testing.assert_array_equal(st.topk_ws[fam], pf.topk_ws[fam],
                                      err_msg=fam)
        np.testing.assert_array_equal(st.topk_index[fam],
                                      pf.topk_index[fam], err_msg=fam)


def test_zero_feasible_configs_raise_descriptive_error():
    """A grid whose smallest per-resource options overshoot the budget
    must raise (naming the family and the violated constraint) instead of
    silently returning -inf scores / -1 indices for downstream argmax to
    consume."""
    wls = random_workloads(2, 2, seed=0)
    opts = StaticOptions(cache_options=(24.0, 32.0),
                         cache_budget_per_app=16.0)
    fams = {"cache_only": FamilySpec(manage_cache=True)}
    with pytest.raises(InfeasibleGridError) as exc:
        search_static(wls, families=fams, options=opts)
    msg = str(exc.value)
    assert "cache_only" in msg and "cache" in msg and "budget" in msg
    # the numpy backend validates identically
    with pytest.raises(InfeasibleGridError):
        search_static(wls, families=fams, options=opts, backend="numpy")
    # an unmanaged resource pinned above its budget trips the same guard
    with pytest.raises(InfeasibleGridError, match="bandwidth"):
        search_static(
            wls, families={"c": FamilySpec(manage_cache=True)},
            options=StaticOptions(bw_fixed=40.0, bw_budget_per_app=4.0))
    assert issubclass(InfeasibleGridError, ValueError)


def test_empty_topk_slot_index_refuses_config_lookup():
    """Index -1 (k beyond the feasible count) must not silently wrap to
    the last grid row when asked for its allocation."""
    wls = random_workloads(2, 2, seed=1)
    fams = {"equal_on": FIG5_FAMILIES["equal_on"]}  # 1 feasible config
    res = search_static(wls, families=fams, k=3)
    assert (res.topk_index["equal_on"][:, 1:] == -1).all()
    with pytest.raises(IndexError, match="top-k slot"):
        res.grids["equal_on"].config(res.topk_index["equal_on"])
    # valid indices keep working
    cfg = res.best_config("equal_on")
    assert cfg["cache_units"].shape == (2, 2)


def test_all3_dominates_every_subset_per_workload():
    """The potential-study invariant: the all-three grid is a superset of
    every subset family's grid, so its best is >= per workload."""
    wls = random_workloads(5, 3, seed=11)
    res = search_static(wls)
    all3 = res.best_ws("cache+bw+pref")
    for fam in res.family_names:
        assert (all3 >= res.best_ws(fam) - 1e-9).all(), fam


def test_backend_dispatch_validates():
    wls = random_workloads(2, 2, seed=0)
    with pytest.raises(ValueError):
        search_static(wls, backend="tpu")
    with pytest.raises(ValueError):
        search_static(wls, k=0)
    with pytest.raises(ValueError):
        search_static(wls, families={})
    with pytest.raises(ValueError):
        search_static([["lbm", "gcc"], ["mcf"]])  # ragged sizes


# --------------------------------------------------------------------- #
# properties (hypothesis)
# --------------------------------------------------------------------- #


@settings(max_examples=15, deadline=None)
@given(n=st.integers(min_value=1, max_value=3),
       c_lo=st.integers(min_value=4, max_value=16),
       c_hi=st.integers(min_value=17, max_value=48),
       b_hi=st.floats(min_value=2.0, max_value=8.0),
       cache_budget=st.integers(min_value=16, max_value=80),
       bw_budget=st.floats(min_value=2.0, max_value=20.0))
def test_enumerated_grids_satisfy_sum_feasibility(n, c_lo, c_hi, b_hi,
                                                  cache_budget, bw_budget):
    """Every enumerated config satisfies both budget constraints, and the
    feasible count matches an itertools brute force."""
    cache_opts = [(float(c_lo), float(c_hi))] * n
    bw_opts = [(1.0, float(b_hi))] * n
    pf_opts = [(0.0, 1.0)] * n
    brute = sum(
        1
        for c in itertools.product(*cache_opts)
        for b in itertools.product(*bw_opts)
        for _ in itertools.product(*pf_opts)
        if sum(c) <= cache_budget + 1e-9 and sum(b) <= bw_budget + 1e-9
    )
    if brute == 0:
        with pytest.raises(ValueError):
            enumerate_grid(cache_opts, bw_opts, pf_opts,
                           cache_budget=cache_budget, bw_budget=bw_budget)
        return
    grid = enumerate_grid(cache_opts, bw_opts, pf_opts,
                          cache_budget=cache_budget, bw_budget=bw_budget)
    assert grid.valid.all()
    assert grid.n_configs == brute
    assert (grid.cache.sum(axis=-1) <= cache_budget + 1e-9).all()
    assert (grid.bandwidth.sum(axis=-1) <= bw_budget + 1e-9).all()
    # padding appends masked rows only
    padded = grid.pad_to(7)
    assert len(padded.valid) % 7 == 0
    assert padded.n_configs == brute
    assert not padded.valid[grid.n_configs:].any()


def test_padding_mask_never_lets_a_masked_config_win():
    """Tiny chunks force grid padding; the pad rows copy the last
    (feasible, possibly high-speedup) config but are masked — they must
    never surface in the top-k."""
    wls = random_workloads(2, 2, seed=0)
    res = search_static(wls, k=5, chunk_elements=8)
    ref = search_static(wls, k=5, backend="numpy")
    for fam in res.family_names:
        n_configs = res.grids[fam].n_configs
        ws, idx = res.topk_ws[fam], res.topk_index[fam]
        finite = np.isfinite(ws)
        assert (idx[finite] >= 0).all() and (idx[finite] < n_configs).all()
        assert (idx[~finite] == -1).all()
        # chunked+padded result == unchunked numpy result
        np.testing.assert_allclose(ws[finite].reshape(-1),
                                   ref.topk_ws[fam][finite].reshape(-1),
                                   rtol=1e-5, err_msg=fam)
        np.testing.assert_array_equal(idx, ref.topk_index[fam],
                                      err_msg=fam)


def test_infeasible_options_never_win():
    """An option value that can only appear in over-budget combos never
    shows up in a winning config."""
    opts = StaticOptions(cache_options=(8.0, 64.0),
                         cache_budget_per_app=16.0)
    fam = {"all3": FamilySpec(manage_cache=True, manage_bw=True,
                              manage_pf=True)}
    wls = random_workloads(2, 2, seed=6)
    res = search_static(wls, families=fam, options=opts, k=3)
    # budget = 32 for n=2: any combo containing 64 sums > 32.
    assert (res.grids["all3"].cache <= 8.0).all()
    assert (res.best_config("all3")["cache_units"] <= 8.0).all()


@settings(max_examples=6, deadline=None)
@given(k=st.integers(min_value=1, max_value=6),
       seed=st.integers(min_value=0, max_value=5))
def test_topk_sorted_and_deduplicated(k, seed):
    """Top-k is sorted descending with distinct config indices; unused
    slots (k beyond the feasible count) are -inf / -1."""
    wls = random_workloads(2, 2, seed=seed)
    fams = {"bw+pref": FIG5_FAMILIES["bw+pref"],
            "cache+bw+pref": FIG5_FAMILIES["cache+bw+pref"]}
    res = search_static(wls, families=fams, k=k)
    for fam in res.family_names:
        ws, idx = res.topk_ws[fam], res.topk_index[fam]
        assert ws.shape == idx.shape == (2, k)
        assert (np.diff(ws, axis=-1) <= 1e-12).all(), fam
        for row_ws, row_idx in zip(ws, idx):
            finite = np.isfinite(row_ws)
            assert len(set(row_idx[finite])) == finite.sum(), fam
            assert (row_idx[~finite] == -1).all(), fam
            assert finite.sum() == min(k, res.grids[fam].n_configs)


def test_arbitrary_napp_workloads_and_custom_grids():
    """Not just the paper's 4-app/3-level setup: 5-app workloads on a
    user-supplied finer grid search end to end."""
    opts = StaticOptions(cache_options=(8.0, 16.0, 24.0),
                         bw_options=(2.0, 5.0))
    wls = random_workloads(2, 5, seed=8)
    res = search_static(wls, families={"all3": FamilySpec(True, True, True)},
                        options=opts, k=2, backend="jax")
    ref = search_static(wls, families={"all3": FamilySpec(True, True, True)},
                        options=opts, k=2, backend="numpy")
    np.testing.assert_allclose(res.topk_ws["all3"], ref.topk_ws["all3"],
                               rtol=1e-5)
    # Workload 1 draws zeusmp twice: configs that swap its two allocations
    # tie in exact arithmetic, and float64 op ordering may rank such a pair
    # either way.  Where an index differs, the golden must score the
    # device's pick equal to its own (to 1e-12), i.e. an exact tie.
    from repro.sim import memsys
    from repro.sim.apps import stack
    from repro.sim.static_search import FIG5_ITERS

    grid = ref.grids["all3"]
    for wi in range(len(wls)):
        got, want = res.topk_index["all3"][wi], ref.topk_index["all3"][wi]
        diff = got != want
        if not diff.any():
            continue
        idx = np.concatenate([got[diff], want[diff]])
        ss = memsys.evaluate(
            stack(wls[wi]), grid.cache[idx], grid.bandwidth[idx],
            grid.prefetch[idx], total_cache_units=grid.total_cache_units,
            total_bandwidth_gbps=grid.total_bandwidth_gbps,
            iters=FIG5_ITERS)
        ws = np.mean(ss.ipc / ref.baseline_ipc[wi], axis=-1)
        np.testing.assert_allclose(ws[: diff.sum()], ws[diff.sum():],
                                   rtol=1e-12)
    cfg = res.best_config("all3")
    assert cfg["cache_units"].shape == (2, 5)
    assert (cfg["cache_units"].sum(axis=-1) <= 16.0 * 5 + 1e-9).all()
    assert (cfg["bandwidth_gbps"].sum(axis=-1) <= 4.0 * 5 + 1e-9).all()


# --------------------------------------------------------------------- #
# multi-objective (Pareto) mode + policy-registry grids
# --------------------------------------------------------------------- #


def _brute_force_front(res, fam, wi):
    """O(C^2) domination enumeration over the WHOLE grid — independent of
    the fold's sort-and-running-max shortcut.  Returns (ws, fairness,
    index) rows sorted descending by ws, exact duplicates deduplicated to
    the lowest config index (the fold's documented tie-break)."""
    from repro.sim import memsys
    from repro.sim.apps import stack
    from repro.sim.static_search import FIG5_ITERS

    grid = res.grids[fam]
    arr = stack(res.workloads[wi])
    ss = memsys.evaluate(
        arr, grid.cache, grid.bandwidth, grid.prefetch,
        total_cache_units=grid.total_cache_units,
        total_bandwidth_gbps=grid.total_bandwidth_gbps,
        iters=FIG5_ITERS)
    speedup = ss.ipc / res.baseline_ipc[wi]
    ws = np.mean(speedup, axis=-1)
    fair = np.min(speedup, axis=-1) / np.max(speedup, axis=-1)
    pts = [(ws[i], fair[i], i) for i in range(len(ws)) if grid.valid[i]]
    front, seen = [], set()
    for w_i, f_i, i in pts:
        dominated = any(
            (w_j >= w_i and f_j >= f_i and (w_j > w_i or f_j > f_i))
            for w_j, f_j, _ in pts)
        if dominated or (w_i, f_i) in seen:
            continue
        seen.add((w_i, f_i))
        front.append((w_i, f_i, i))
    front.sort(key=lambda t: (-t[0], t[2]))
    return front


def test_pareto_front_matches_brute_force_enumeration():
    """Acceptance gate: the multi-objective fold's front equals an O(C^2)
    brute-force domination enumeration over the small grid — same
    members, same (ws, fairness) values, same config indices, ws
    descending / fairness ascending down the slots."""
    wls = random_workloads(2, 3, seed=6)
    fams = {"cache+bw": FIG5_FAMILIES["cache+bw"]}
    res = search_static(wls, families=fams, k=16, backend="numpy",
                        multi_objective=True)
    assert res.multi_objective and res.topk_fairness is not None
    for wi in range(2):
        front = _brute_force_front(res, "cache+bw", wi)
        assert 2 <= len(front) <= res.k  # a real front, never truncated
        got_idx = res.topk_index["cache+bw"][wi]
        valid = got_idx >= 0
        assert valid.sum() == len(front)
        np.testing.assert_array_equal(got_idx[valid],
                                      [i for _, _, i in front])
        np.testing.assert_allclose(res.topk_ws["cache+bw"][wi][valid],
                                   [w for w, _, _ in front], rtol=0)
        np.testing.assert_allclose(res.topk_fairness["cache+bw"][wi][valid],
                                   [f for _, f, _ in front], rtol=0)
        # front shape: ws strictly decreasing, fairness strictly increasing
        ws_v = res.topk_ws["cache+bw"][wi][valid]
        f_v = res.topk_fairness["cache+bw"][wi][valid]
        assert (np.diff(ws_v) < 0).all() and (np.diff(f_v) > 0).all()
        # empty slots carry the documented sentinels
        assert (res.topk_ws["cache+bw"][wi][~valid] == -np.inf).all()
        assert (res.topk_fairness["cache+bw"][wi][~valid] == -np.inf).all()


def test_pareto_jax_matches_numpy_backend():
    """The chunked device-side Pareto fold is exact: identical front
    members, values and indices to the whole-grid numpy reference."""
    wls = random_workloads(3, 2, seed=5)
    fams = {"cache+bw": FIG5_FAMILIES["cache+bw"],
            "cache+bw+pref": FIG5_FAMILIES["cache+bw+pref"]}
    jx = search_static(wls, families=fams, k=6, multi_objective=True)
    ref = search_static(wls, families=fams, k=6, backend="numpy",
                        multi_objective=True)
    for fam in jx.family_names:
        np.testing.assert_array_equal(jx.topk_index[fam],
                                      ref.topk_index[fam], err_msg=fam)
        np.testing.assert_allclose(jx.topk_ws[fam], ref.topk_ws[fam],
                                   rtol=1e-12, err_msg=fam)
        np.testing.assert_allclose(jx.topk_fairness[fam],
                                   ref.topk_fairness[fam], rtol=1e-12,
                                   err_msg=fam)


def test_knee_index_picks_balanced_tradeoff():
    """Synthetic 3-member front: the knee is the middle member (closest
    to utopia after min-max normalization), not either extreme; a
    scalar result refuses the query."""
    from repro.sim.static_search import StaticSearchResult

    res = StaticSearchResult(
        family_names=["f"], workloads=[["a", "b"]], grids={},
        topk_ws={"f": np.array([[3.0, 2.0, 1.0], [5.0, -np.inf, -np.inf]])},
        topk_index={"f": np.array([[5, 7, 9], [2, -1, -1]])},
        baseline_ipc=np.ones((2, 2)), backend="numpy", k=3,
        topk_fairness={"f": np.array([[0.1, 0.9, 1.0],
                                      [0.4, -np.inf, -np.inf]])},
        multi_objective=True)
    # normalized: (1,0), (.5,.889), (0,1) -> middle is nearest to (1,1);
    # the single-member front degenerates to its only (best-ws) member.
    np.testing.assert_array_equal(res.knee_index("f"), [7, 2])

    scalar = search_static(random_workloads(2, 2, seed=0), k=2,
                           backend="numpy")
    with pytest.raises(ValueError, match="multi_objective"):
        scalar.knee_index("cache+bw+pref")


def test_registry_families_expose_policy_grids():
    """Every registered manager family converts to a FamilySpec; the new
    policy families carry their documented knobs (auction/qos search
    cache+bw, bank bw searches bandwidth over 4 banks)."""
    fams = registry_families()
    from repro.sim import policies
    assert set(fams) == set(policies.manager_names())
    assert fams["auction"].manage_cache and fams["auction"].manage_bw
    assert fams["qos"].manage_cache and fams["qos"].manage_bw
    assert not fams["bank bw"].manage_cache and fams["bank bw"].manage_bw
    assert fams["bank bw"].bandwidth_banks == 4
    sub = registry_families(["CBP", "bank bw"])
    assert list(sub) == ["CBP", "bank bw"]


def test_banked_family_search_end_to_end():
    """The bank-aware bandwidth model threads through the search: numpy
    and brute-force direct evaluation agree exactly, and banking shifts
    the scores away from the flat (1-bank) model."""
    from repro.sim import memsys

    wls = random_workloads(2, 2, seed=9)
    fams = registry_families(["bank bw"])
    res = search_static(wls, families=fams, k=2, backend="numpy")
    flat = search_static(
        wls, families={"bank bw": FamilySpec(manage_bw=True)}, k=2,
        backend="numpy")
    grid = res.grids["bank bw"]
    for wi in range(2):
        from repro.sim.apps import stack
        ss = memsys.evaluate(
            stack(wls[wi]), grid.cache, grid.bandwidth, grid.prefetch,
            total_cache_units=grid.total_cache_units,
            total_bandwidth_gbps=grid.total_bandwidth_gbps,
            bandwidth_banks=4, iters=40)
        ws = np.mean(ss.ipc / res.baseline_ipc[wi], axis=-1)
        best = np.argsort(-ws, kind="stable")[:2]
        np.testing.assert_array_equal(res.topk_index["bank bw"][wi], best)
        np.testing.assert_allclose(res.topk_ws["bank bw"][wi], ws[best],
                                   rtol=0)
    assert not np.allclose(res.topk_ws["bank bw"], flat.topk_ws["bank bw"])


# --------------------------------------------------------------------- #
# shared baseline construction + figure entry points
# --------------------------------------------------------------------- #


def test_equal_share_is_the_single_baseline_construction():
    units, bw = equal_share(16, 256, 64.0)
    assert (units == 16).all()
    np.testing.assert_allclose(bw, 4.0)
    # the Fig. 5 protocol shape: 4 apps, 16 units / 4 GB/s each
    units, bw = equal_share(4, 64, 16.0)
    assert (units == 16).all()
    np.testing.assert_allclose(bw, 4.0)


def test_equal_on_geomean_pinned():
    """Regression pin for the shared equal-share baseline: if the Fig. 5
    baseline construction drifts from the sweep baseline helper
    (repro.sim.equal_share), this moves."""
    wls = random_workloads(8, 4, seed=7)
    res = search_static(wls, families={"equal_on": FIG5_FAMILIES["equal_on"]},
                        backend="numpy")
    assert res.geomean("equal_on") == pytest.approx(1.11575462098291,
                                                    abs=1e-6)


def test_fig5_potential_smoke_orders_all3_above_subsets(monkeypatch,
                                                        tmp_path):
    """Tier-1 coverage for the benchmark entry point: the paper's
    headline ordering (all-three >= best two-resource subset) and the
    emitted record shape."""
    import benchmarks.common as bench_common
    from benchmarks.paper_figs import fig5_potential
    monkeypatch.setattr(bench_common, "RESULTS", tmp_path)
    derived = fig5_potential(n_workloads=8)
    assert derived["n_workloads"] == 8
    best2 = max(derived[f"geo_{f}"] for f in FIG5_TWO_RESOURCE)
    assert derived["geo_cache+bw+pref"] >= best2 - 1e-9
    assert derived["all3_vs_best2"] >= 0.0
    record = json.loads((tmp_path / "fig5_potential.json").read_text())
    assert record["derived"]["backend"] == "jax"


def test_family_grid_matches_exhaustive_best_combo_count():
    """The subsystem enumerates exactly the reference combo list."""
    n = 4
    grid = family_grid(FamilySpec(True, True, True), n)
    caches = [c for c in itertools.product(*[(8, 16, 32)] * n)
              if sum(c) <= 16 * n]
    bws = [b for b in itertools.product(*[(2.0, 4.0, 6.0)] * n)
           if sum(b) <= 4.0 * n]
    assert grid.n_configs == len(caches) * len(bws) * 2 ** n
    # spot-check enumeration order at both ends
    np.testing.assert_allclose(grid.cache[0], caches[0])
    np.testing.assert_allclose(grid.cache[-1], caches[-1])
    np.testing.assert_allclose(grid.bandwidth[0], bws[0])
    np.testing.assert_allclose(grid.prefetch[0], 0.0)
    np.testing.assert_allclose(grid.prefetch[-1], 1.0)


# --------------------------------------------------------------------- #
# multi-device sharding
# --------------------------------------------------------------------- #

_SHARD_SCRIPT = """
import json, sys
import numpy as np
import jax
from repro.sim.static_search import search_static
from repro.sim.workloads import random_workloads
assert jax.device_count() == 8, jax.device_count()
res = search_static(random_workloads(3, 3, seed=4), k=2)
json.dump({f: {"ws": res.topk_ws[f].tolist(),
               "idx": res.topk_index[f].tolist()}
           for f in res.family_names}, sys.stdout)
"""


@pytest.mark.slow
def test_workload_axis_shards_across_forced_host_devices():
    """The same search on 8 forced host devices (workload axis sharded
    via repro.distributed.shard_rows, padded 3 -> 8) matches the
    single-device run to float64 round-off, identical config indices."""
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    env["XLA_FLAGS"] = flags.strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(repo, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    sharded = json.loads(proc.stdout)

    ref = search_static(random_workloads(3, 3, seed=4), k=2)
    for fam in ref.family_names:
        np.testing.assert_allclose(
            np.asarray(sharded[fam]["ws"]), ref.topk_ws[fam],
            rtol=1e-12, atol=1e-12, err_msg=fam)
        np.testing.assert_array_equal(
            np.asarray(sharded[fam]["idx"]), ref.topk_index[fam],
            err_msg=fam)
