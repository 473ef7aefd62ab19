"""Batched static-allocation search (the Fig. 5 potential-study substrate).

The paper's potential study (§2.3 / Fig. 5) exhaustively searches static
(cache, bandwidth, prefetch) allocations per workload and per manager
*family* (the subset of resources a manager may move) to show that
coordinating all three resources beats any two-resource subset.  The old
path looped ``benchmarks.paper_figs._exhaustive_best`` on the host — one
vectorized numpy solve per (workload, family), ~3840 host dispatches for
the 640-workload study.  This module turns each family into ONE jitted
device program:

* the constrained config grid is enumerated on the host
  (:func:`enumerate_grid` — per-resource option products, sum-feasibility
  filtered, in ``itertools.product`` order) and padded to a chunk multiple
  with a validity mask;
* the program scans config chunks on device, evaluating the batched
  interval model (:mod:`repro.sim.memsys_jax`) for every (workload,
  config) pair in the chunk and folding a running top-k of weighted
  speedups — memory stays bounded at ``n_workloads x chunk`` regardless
  of grid size;
* the workload axis shards across devices via
  :func:`repro.distributed.shard_rows`, exactly like the fused Fig. 8
  timelines.

A full :func:`search_static` is therefore ``len(families)`` device
programs plus one shared baseline evaluation (counter:
:func:`repro.core.device_dispatches`).

Parity contract: ``backend="numpy"`` runs the same search on the numpy
golden reference (:func:`repro.sim.memsys.evaluate`, one host solve per
workload — the ``_exhaustive_best`` protocol); the JAX backend must match
it within 1e-5 relative weighted speedup and return the SAME argmax
config under the documented tie-break (enforced by
``tests/test_static_search.py``).

Tie-breaks: among configs with equal weighted speedup the LOWEST
enumeration index wins, where enumeration order is ``itertools.product``
nesting — cache combinations outermost, then bandwidth, then prefetch,
each with the last application varying fastest (the `_exhaustive_best`
combo order).  Top-k results are sorted descending by weighted speedup
with distinct config indices; slots beyond the number of feasible
configs hold ``-inf`` / index ``-1``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import numpy as np

from repro.sim import memsys
from repro.sim.apps import MODEL_FIELDS, AppArrays, stack_mixes
from repro.sim.runner import equal_share

#: Fixed-point iterations of the Fig. 5 protocol (fewer than the plant's
#: 60: static allocations converge fast and the reference always used 40).
FIG5_ITERS = 40

#: Target elements (workloads x configs x apps) per on-device scan step;
#: bounds peak memory at a few hundred MB of f64 temporaries.
CHUNK_ELEMENTS = 1 << 21


class InfeasibleGridError(ValueError):
    """A static config grid has zero feasible configurations.

    Raised with the violated constraint (and, from :func:`search_static`,
    the family name) instead of silently searching an empty grid — an
    empty grid's top-k would be all ``-inf`` scores and ``-1`` indices,
    which downstream argmax/``config`` lookups consume as garbage.
    """


@dataclasses.dataclass(frozen=True)
class FamilySpec:
    """Which resources a Fig. 5 family may allocate statically.

    Unmanaged resources pin to the equal-share fixed point
    (``StaticOptions.cache_fixed`` / ``bw_fixed``); an unmanaged
    prefetcher is off unless ``pf_all_on`` forces it on for everyone.
    """

    manage_cache: bool = False
    manage_bw: bool = False
    manage_pf: bool = False
    pf_all_on: bool = False
    bandwidth_banks: int = 1     # >1: banked-token bandwidth regime


#: The Fig. 5 manager families (paper §2.3), insertion order = plot order.
FIG5_FAMILIES: Dict[str, FamilySpec] = {
    "equal_on": FamilySpec(pf_all_on=True),
    "only_pref": FamilySpec(manage_pf=True),
    "bw+pref": FamilySpec(manage_bw=True, manage_pf=True),
    "cache+bw": FamilySpec(manage_cache=True, manage_bw=True),
    "cache+pref": FamilySpec(manage_cache=True, manage_pf=True),
    "cache+bw+pref": FamilySpec(manage_cache=True, manage_bw=True,
                                manage_pf=True),
}

#: The two-resource subsets the all-three family is compared against.
FIG5_TWO_RESOURCE = ("bw+pref", "cache+bw", "cache+pref")


def registry_families(
        names: Optional[Sequence[str]] = None) -> Dict[str, FamilySpec]:
    """Manager families' static-grid vocabularies as :class:`FamilySpec`.

    Converts the policy registry's plain ``static_grid`` kwargs
    (:mod:`repro.sim.policies`) into the search's family specs, so
    ``search_static(families=registry_families(["CBP", "bank bw"]))``
    explores exactly the knobs each manager family may move.  Default:
    every registered family.
    """
    from repro.sim import policies

    resolved = policies.manager_names() if names is None else list(names)
    out: Dict[str, FamilySpec] = {}
    for name in resolved:
        fam = policies.get_family(name)   # UnknownManagerError on a typo
        out[name] = FamilySpec(**(fam.static_grid or {}))
    return out


@dataclasses.dataclass(frozen=True)
class StaticOptions:
    """The static design-space option values (paper §2.3 defaults).

    Budgets are per application: a workload of ``n`` apps searches under
    ``sum(cache) <= cache_budget_per_app * n`` (ditto bandwidth), and the
    budgets double as the model's total capacities — exactly the
    ``_exhaustive_best`` protocol.  Replace the option tuples for finer
    or larger grids; they need not contain the fixed points.
    """

    cache_options: Tuple[float, ...] = (8.0, 16.0, 32.0)
    cache_fixed: float = 16.0
    bw_options: Tuple[float, ...] = (2.0, 4.0, 6.0)
    bw_fixed: float = 4.0
    cache_budget_per_app: float = 16.0
    bw_budget_per_app: float = 4.0

    def per_app(self, spec: FamilySpec, n: int):
        """Per-application option tuples for one family."""
        cache = (tuple(float(c) for c in self.cache_options)
                 if spec.manage_cache else (float(self.cache_fixed),))
        bw = (tuple(float(b) for b in self.bw_options)
              if spec.manage_bw else (float(self.bw_fixed),))
        pf = ((0.0, 1.0) if spec.manage_pf
              else ((1.0,) if spec.pf_all_on else (0.0,)))
        return [cache] * n, [bw] * n, [pf] * n


@dataclasses.dataclass
class StaticGrid:
    """Feasible static configurations, one row per (cache, bw, pf) combo.

    ``cache`` / ``bandwidth`` / ``prefetch`` are ``(C, n)``; ``valid`` is
    ``(C,)`` and is all-True straight out of :func:`enumerate_grid` —
    :meth:`pad_to` appends masked copies of the last row so the device
    scan sees a rectangular chunk grid, and the search reductions ignore
    every ``valid == False`` row.
    """

    cache: np.ndarray
    bandwidth: np.ndarray
    prefetch: np.ndarray
    valid: np.ndarray
    total_cache_units: float
    total_bandwidth_gbps: float

    @property
    def n_configs(self) -> int:
        """Feasible (unmasked) configurations."""
        return int(self.valid.sum())

    @property
    def n_apps(self) -> int:
        return int(self.cache.shape[-1])

    def pad_to(self, multiple: int) -> "StaticGrid":
        """Pad rows to a multiple of ``multiple`` with ``valid=False``."""
        c = len(self.valid)
        pad = -(-c // multiple) * multiple - c
        if pad == 0:
            return self

        def ext(a: np.ndarray) -> np.ndarray:
            return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])

        return dataclasses.replace(
            self, cache=ext(self.cache), bandwidth=ext(self.bandwidth),
            prefetch=ext(self.prefetch),
            valid=np.concatenate([self.valid, np.zeros(pad, dtype=bool)]))

    def config(self, index) -> Dict[str, np.ndarray]:
        """Allocation arrays for (an array of) config indices.

        Index ``-1`` marks an empty top-k slot (fewer feasible configs
        than ``k``); refusing it here beats numpy's silent wrap-around to
        the last grid row, which would hand the caller an allocation that
        never won anything.
        """
        idx = np.asarray(index)
        if idx.size and (idx < 0).any():
            raise IndexError(
                "config index -1 marks an empty top-k slot (fewer "
                "feasible configurations than k) — no allocation exists "
                "for it")
        return {
            "cache_units": self.cache[idx],
            "bandwidth_gbps": self.bandwidth[idx],
            "prefetch_on": self.prefetch[idx],
        }


def _options_product(opts: Sequence[Tuple[float, ...]]) -> np.ndarray:
    """All per-app combinations, ``itertools.product`` order, ``(K, n)``."""
    grids = np.meshgrid(*[np.asarray(o, np.float64) for o in opts],
                        indexing="ij")
    return np.stack([g.reshape(-1) for g in grids], axis=-1)


def enumerate_grid(
    cache_options: Sequence[Tuple[float, ...]],
    bw_options: Sequence[Tuple[float, ...]],
    pf_options: Sequence[Tuple[float, ...]],
    *,
    cache_budget: float,
    bw_budget: float,
) -> StaticGrid:
    """Enumerate the feasible static grid for one workload size.

    Each ``*_options`` entry is the option tuple of one application.
    Per-resource combinations whose sum exceeds the budget are dropped
    (sum-feasibility), then the three resources cross — preserving the
    reference enumeration order (cache outermost, then bandwidth, then
    prefetch, last application fastest).
    """
    n = len(cache_options)
    if not (len(bw_options) == n and len(pf_options) == n):
        raise ValueError(
            f"per-app option lists disagree on n: {len(cache_options)}, "
            f"{len(bw_options)}, {len(pf_options)}")
    caches = _options_product(cache_options)
    caches = caches[caches.sum(axis=-1) <= cache_budget + 1e-9]
    bws = _options_product(bw_options)
    bws = bws[bws.sum(axis=-1) <= bw_budget + 1e-9]
    pfs = _options_product(pf_options)
    if len(caches) == 0 or len(bws) == 0:
        violations = []
        for label, opts, budget, combos in (
                ("cache", cache_options, cache_budget, caches),
                ("bandwidth", bw_options, bw_budget, bws)):
            if len(combos) == 0:
                min_sum = (sum(min(o) for o in opts)
                           if all(len(o) for o in opts) else None)
                violations.append(
                    f"{label}: empty per-app option tuple" if min_sum is None
                    else f"{label}: smallest per-app options sum to "
                         f"{min_sum} > budget {budget}")
        raise InfeasibleGridError(
            "no feasible configuration — " + "; ".join(violations))
    cc, cb, cp = len(caches), len(bws), len(pfs)
    return StaticGrid(
        cache=np.repeat(caches, cb * cp, axis=0),
        bandwidth=np.tile(np.repeat(bws, cp, axis=0), (cc, 1)),
        prefetch=np.tile(pfs, (cc * cb, 1)),
        valid=np.ones(cc * cb * cp, dtype=bool),
        total_cache_units=float(cache_budget),
        total_bandwidth_gbps=float(bw_budget),
    )


def family_grid(spec: FamilySpec, n: int,
                options: Optional[StaticOptions] = None) -> StaticGrid:
    """The constrained config grid of one family for ``n``-app workloads."""
    options = options or StaticOptions()
    cache_opts, bw_opts, pf_opts = options.per_app(spec, n)
    return enumerate_grid(
        cache_opts, bw_opts, pf_opts,
        cache_budget=options.cache_budget_per_app * n,
        bw_budget=options.bw_budget_per_app * n)


@dataclasses.dataclass
class StaticSearchResult:
    """Per-(family, workload) best static allocations.

    ``topk_ws`` / ``topk_index`` are ``(W, k)`` — sorted descending by
    weighted speedup, distinct config indices into ``grids[family]``,
    with ``-inf`` / ``-1`` filling slots beyond the feasible count.

    With ``multi_objective`` the slots hold the Pareto front over
    (weighted speedup, min-fairness) instead of the scalar top-k:
    still sorted descending by weighted speedup — so fairness strictly
    increases down the slots — with ``topk_fairness`` carrying each
    front member's min-fairness and ``k`` doubling as the front
    capacity (fronts wider than ``k`` keep their ``k`` best-ws members).
    """

    family_names: List[str]
    workloads: List[List[str]]
    grids: Dict[str, StaticGrid]
    topk_ws: Dict[str, np.ndarray]
    topk_index: Dict[str, np.ndarray]
    baseline_ipc: np.ndarray            # (W, n)
    backend: str
    k: int
    topk_fairness: Optional[Dict[str, np.ndarray]] = None   # (W, k)
    multi_objective: bool = False

    def knee_index(self, family: str) -> np.ndarray:
        """Per-workload config index of the front's knee point, ``(W,)``.

        The knee is the front member closest (Euclidean) to the utopia
        point after min-max normalizing both objectives over the front —
        the standard balanced-trade-off pick.  Ties and degenerate
        (single-member or zero-span) fronts resolve toward the
        best-weighted-speedup end.  Multi-objective results only.
        """
        if not self.multi_objective:
            raise ValueError(
                "knee_index needs a multi_objective=True search result")
        ws = np.asarray(self.topk_ws[family], dtype=np.float64)
        f = np.asarray(self.topk_fairness[family], dtype=np.float64)
        idx = np.asarray(self.topk_index[family])
        valid = idx >= 0

        def norm(x):
            lo = np.min(np.where(valid, x, np.inf), axis=-1, keepdims=True)
            hi = np.max(np.where(valid, x, -np.inf), axis=-1, keepdims=True)
            span = hi - lo
            return np.where(span > 0, (x - lo) / np.where(span > 0, span, 1.0),
                            1.0)

        dist = (1.0 - norm(ws)) ** 2 + (1.0 - norm(f)) ** 2
        dist = np.where(valid, dist, np.inf)
        pos = np.argmin(dist, axis=-1)       # first minimum: best-ws end
        return np.take_along_axis(idx, pos[:, None], axis=-1)[:, 0]

    @property
    def n_workloads(self) -> int:
        return int(self.baseline_ipc.shape[0])

    def best_ws(self, family: str) -> np.ndarray:
        """Best weighted speedup per workload, shape ``(W,)``."""
        return self.topk_ws[family][:, 0]

    def best_index(self, family: str) -> np.ndarray:
        return self.topk_index[family][:, 0]

    def best_config(self, family: str) -> Dict[str, np.ndarray]:
        """Winning allocation arrays per workload, each ``(W, n)``."""
        return self.grids[family].config(self.best_index(family))

    def geomean(self, family: str) -> float:
        """Geometric-mean best weighted speedup over workloads."""
        return float(np.exp(np.mean(np.log(self.best_ws(family)))))

    def frac_at_least(self, family: str, threshold: float = 1.10) -> float:
        """Fraction of workloads at or above ``threshold`` (Fig. 5b)."""
        return float(np.mean(self.best_ws(family) >= threshold))

    def summary(self) -> Dict[str, float]:
        return {name: round(self.geomean(name), 4)
                for name in self.family_names}


def _resolve_families(
    families: Optional[Mapping[str, Union[FamilySpec, Mapping[str, bool]]]],
) -> Dict[str, FamilySpec]:
    if families is None:
        return dict(FIG5_FAMILIES)
    out: Dict[str, FamilySpec] = {}
    for name, spec in families.items():
        out[name] = spec if isinstance(spec, FamilySpec) else FamilySpec(**spec)
    if not out:
        raise ValueError("families must be non-empty")
    return out


def _row_apps(stacked: AppArrays, wi: int) -> AppArrays:
    names = stacked.names[wi] if stacked.names else []
    return AppArrays(
        names=list(names),
        **{f: np.asarray(getattr(stacked, f))[wi] for f in MODEL_FIELDS})


# --------------------------------------------------------------------- #
# numpy golden-reference backend
# --------------------------------------------------------------------- #

def _pareto_topk(ws: np.ndarray, fairness: np.ndarray, index: np.ndarray,
                 k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The ``k`` best-ws Pareto-front members of one candidate set.

    Sort by (ws desc, fairness desc, index asc); an entry is on the front
    iff its fairness strictly exceeds the exclusive running max — which
    drops strictly dominated entries, weakly dominated ones (equal in one
    objective, worse in the other) and exact duplicates (keeping the
    lowest index) in one rule.  Masked candidates carry ``-inf`` in both
    objectives and can never be kept.  The JAX fold
    (:func:`_family_scan`) applies the identical rule per merge step.
    """
    order = np.lexsort((index, -fairness, -ws))
    s_ws, s_f, s_idx = ws[order], fairness[order], index[order]
    run_max = np.concatenate(
        [[-np.inf], np.maximum.accumulate(s_f)[:-1]])
    kept_ws = np.where(s_f > run_max, s_ws, -np.inf)
    sel = np.argsort(-kept_ws, kind="stable")[:k]
    out_ws, out_f, out_idx = kept_ws[sel], s_f[sel], s_idx[sel]
    empty = np.isinf(out_ws)
    pad = k - len(sel)
    return (np.concatenate([out_ws, np.full(pad, -np.inf)]),
            np.concatenate([np.where(empty, -np.inf, out_f),
                            np.full(pad, -np.inf)]),
            np.concatenate([np.where(empty, -1, out_idx),
                            np.full(pad, -1, out_idx.dtype)]))


def _search_numpy_family(
    apps_rows: List[AppArrays],
    grid: StaticGrid,
    baseline_ipc: np.ndarray,
    k: int,
    iters: int,
    banks: int = 1,
    multi: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One host solve per workload over the whole (unpadded) grid."""
    w = len(apps_rows)
    top_ws = np.full((w, k), -np.inf)
    top_f = np.full((w, k), -np.inf)
    top_idx = np.full((w, k), -1, dtype=np.int64)
    for wi, arr in enumerate(apps_rows):
        ss = memsys.evaluate(
            arr, grid.cache, grid.bandwidth, grid.prefetch,
            total_cache_units=grid.total_cache_units,
            total_bandwidth_gbps=grid.total_bandwidth_gbps,
            bandwidth_banks=banks, iters=iters)
        speedup = ss.ipc / baseline_ipc[wi]
        ws = np.mean(speedup, axis=-1)
        ws = np.where(grid.valid, ws, -np.inf)
        if multi:
            fair = np.min(speedup, axis=-1) / np.max(speedup, axis=-1)
            fair = np.where(grid.valid, fair, -np.inf)
            idx = np.arange(len(ws), dtype=np.int64)
            top_ws[wi], top_f[wi], top_idx[wi] = _pareto_topk(
                ws, fair, idx, k)
            continue
        # Stable descending sort: equal speedups keep enumeration order,
        # i.e. the lowest config index wins (the documented tie-break).
        order = np.argsort(-ws, kind="stable")[:k]
        top_ws[wi, : len(order)] = ws[order]
        top_idx[wi, : len(order)] = order
    return top_ws, top_idx, top_f


# --------------------------------------------------------------------- #
# JAX device backend
# --------------------------------------------------------------------- #

def _family_scan(p, base, tables, k: int, iters: int, banks: int = 1,
                 multi: bool = False):
    """The chunked top-k fold of ONE family, shared by both program shapes.

    ``tables`` holds the family's chunked config grid (``(s, chunk, n)``
    plus validity/index rows); the scan evaluates the interval model for
    the full (workload, chunk) block and folds a running top-k.  Both
    ``lax.top_k`` calls break value ties toward earlier positions, and
    the running entries (earlier chunks = lower config indices) are
    concatenated first, so the global tie-break is "lowest enumeration
    index" — matching the numpy reference's stable argsort.

    With ``multi`` the carry folds the Pareto front over (weighted
    speedup, min-fairness) instead: each step merges the running front
    with the WHOLE chunk under the :func:`_pareto_topk` keep rule
    (sort by ws desc / fairness desc / index asc, keep iff fairness
    strictly beats the exclusive running max) and retains the ``k``
    best-ws survivors — ``k`` is the front capacity.
    """
    import jax
    import jax.numpy as jnp

    from repro.sim import memsys_jax

    total_units = tables["total_cache_units"]
    total_bw = tables["total_bandwidth"]
    llc_extra = tables["llc_extra_cycles"]

    def step(carry, xs):
        c_cache, c_bw, c_pf, c_valid, c_idx = xs
        out = memsys_jax._evaluate_jit(
            p, c_cache, c_bw, c_pf, total_units, total_bw, llc_extra,
            cache_partitioned=True, bandwidth_partitioned=True,
            iters=iters, bandwidth_banks=banks)
        speedup = out[0] / base[:, None, :]                # (W, chunk, n)
        ws = jnp.mean(speedup, axis=-1)                    # (W, chunk)
        ws = jnp.where(c_valid[None, :], ws, -jnp.inf)
        if not multi:
            top_ws, top_idx = carry
            cand_ws, cand_loc = jax.lax.top_k(ws, k)
            cand_idx = c_idx[cand_loc]
            merged_ws = jnp.concatenate([top_ws, cand_ws], axis=-1)
            merged_idx = jnp.concatenate([top_idx, cand_idx], axis=-1)
            top_ws, sel = jax.lax.top_k(merged_ws, k)
            top_idx = jnp.take_along_axis(merged_idx, sel, axis=-1)
            return (top_ws, top_idx), None

        top_ws, top_f, top_idx = carry
        fair = (jnp.min(speedup, axis=-1)
                / jnp.max(speedup, axis=-1))
        fair = jnp.where(c_valid[None, :], fair, -jnp.inf)
        w_rows = ws.shape[0]
        m_ws = jnp.concatenate([top_ws, ws], axis=-1)
        m_f = jnp.concatenate([top_f, fair], axis=-1)
        m_idx = jnp.concatenate(
            [top_idx, jnp.broadcast_to(c_idx, ws.shape)], axis=-1)
        # _pareto_topk, vectorized over workload rows.
        order = jnp.lexsort((m_idx, -m_f, -m_ws), axis=-1)
        s_ws = jnp.take_along_axis(m_ws, order, axis=-1)
        s_f = jnp.take_along_axis(m_f, order, axis=-1)
        s_idx = jnp.take_along_axis(m_idx, order, axis=-1)
        run_max = jnp.concatenate(
            [jnp.full((w_rows, 1), -jnp.inf, s_f.dtype),
             jax.lax.cummax(s_f, axis=1)[:, :-1]], axis=-1)
        kept_ws = jnp.where(s_f > run_max, s_ws, -jnp.inf)
        top_ws, sel = jax.lax.top_k(kept_ws, k)
        top_f = jnp.take_along_axis(s_f, sel, axis=-1)
        top_idx = jnp.take_along_axis(s_idx, sel, axis=-1)
        empty = jnp.isinf(top_ws)
        top_f = jnp.where(empty, -jnp.inf, top_f)
        top_idx = jnp.where(empty, -1, top_idx)
        return (top_ws, top_f, top_idx), None

    w = base.shape[0]
    if multi:
        init = (jnp.full((w, k), -jnp.inf, base.dtype),
                jnp.full((w, k), -jnp.inf, base.dtype),
                jnp.full((w, k), -1, jnp.int32))
    else:
        init = (jnp.full((w, k), -jnp.inf, base.dtype),
                jnp.full((w, k), -1, jnp.int32))
    carry, _ = jax.lax.scan(
        step, init,
        (tables["cache"], tables["bandwidth"], tables["prefetch"],
         tables["valid"], tables["index"]))
    if multi:
        return carry
    return carry[0], carry[1]


def _pack_scan_out(scan_out, suffix: str = "") -> Dict[str, object]:
    if len(scan_out) == 3:
        top_ws, top_f, top_idx = scan_out
        return {f"topk_ws{suffix}": top_ws,
                f"topk_fairness{suffix}": top_f,
                f"topk_index{suffix}": top_idx}
    top_ws, top_idx = scan_out
    return {f"topk_ws{suffix}": top_ws, f"topk_index{suffix}": top_idx}


@functools.lru_cache(maxsize=None)
def _compiled_search(k: int, iters: int, n_shards: int, banks: int,
                     multi: bool):
    """Build the jitted (optionally shard_mapped) ONE-family program.

    Cached per static configuration (``banks`` selects the family's
    bandwidth regime, ``multi`` the Pareto fold); jit retraces on new
    array shapes (different W, n, chunking) as usual.  This is the
    per-family reference path the stacked program is parity-pinned
    against.
    """
    import jax

    from repro import distributed
    from repro.sim import memsys_jax

    def worker(sharded, replicated):
        p = {f: sharded["p_" + f][:, None, :]
             for f in memsys_jax.PARAM_FIELDS}          # (W, 1, n)
        base = sharded["baseline_ipc"]                  # (W, n)
        return _pack_scan_out(
            _family_scan(p, base, replicated, k, iters, banks, multi))

    if n_shards > 1:
        worker = distributed.shard_rows(worker, n_shards)
    return jax.jit(worker)


@functools.lru_cache(maxsize=None)
def _compiled_stacked_search(banks_per_family: Tuple[int, ...], k: int,
                             iters: int, n_shards: int, multi: bool):
    """Build the jitted (optionally shard_mapped) ALL-families program.

    Every family keeps its own chunk shape (and bank count) and runs its
    own :func:`_family_scan` — the family axis concatenates the
    per-family scans *sequentially inside one program*, so each family's
    subcomputation is shape-identical to the per-family path (bit-parity
    by construction) while a full :func:`search_static` drops from
    ``len(families) + 1`` device dispatches to 2.  The workload axis
    shards exactly as before.
    """
    import jax

    from repro import distributed
    from repro.sim import memsys_jax

    def worker(sharded, replicated):
        p = {f: sharded["p_" + f][:, None, :]
             for f in memsys_jax.PARAM_FIELDS}          # (W, 1, n)
        base = sharded["baseline_ipc"]                  # (W, n)
        out = {}
        for fi, banks in enumerate(banks_per_family):
            out.update(_pack_scan_out(
                _family_scan(p, base, replicated[f"family{fi}"], k,
                             iters, banks, multi), str(fi)))
        return out

    if n_shards > 1:
        worker = distributed.shard_rows(worker, n_shards)
    return jax.jit(worker)


def _family_tables(grid: StaticGrid, w_pad: int, k: int,
                   chunk_elements: int) -> Dict[str, np.ndarray]:
    """Chunk one family's config grid into the scan tables it runs over.

    The chunk shape depends only on this family's grid and the padded
    workload count, NOT on which program (per-family or stacked) consumes
    it — that is what keeps the two program shapes bit-identical per
    family.
    """
    n = grid.n_apps
    chunk = max(k, min(len(grid.valid),
                       max(1, chunk_elements // max(1, w_pad * n))))
    padded = grid.pad_to(chunk)
    s = len(padded.valid) // chunk
    return {
        "cache": padded.cache.reshape(s, chunk, n),
        "bandwidth": padded.bandwidth.reshape(s, chunk, n),
        "prefetch": padded.prefetch.reshape(s, chunk, n),
        "valid": padded.valid.reshape(s, chunk),
        "index": np.arange(s * chunk, dtype=np.int32).reshape(s, chunk),
        "total_cache_units": np.float64(grid.total_cache_units),
        "total_bandwidth": np.float64(grid.total_bandwidth_gbps),
        "llc_extra_cycles": np.float64(0.0),
    }


def _search_jax_family(
    sharded: Dict[str, np.ndarray],
    grid: StaticGrid,
    w: int,
    k: int,
    iters: int,
    n_shards: int,
    chunk_elements: int,
    banks: int = 1,
    multi: bool = False,
) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """One device program: chunked grid scan + top-k for one family."""
    from repro.core.dispatch import record_dispatch
    from repro.core.x64 import x64_context

    w_pad = sharded["baseline_ipc"].shape[0]
    replicated = _family_tables(grid, w_pad, k, chunk_elements)
    fn = _compiled_search(k, iters, n_shards, banks, multi)
    record_dispatch()
    with x64_context():
        out = fn(sharded, replicated)
        top_ws = np.asarray(out["topk_ws"])[:w]
        top_idx = np.asarray(out["topk_index"])[:w].astype(np.int64)
        top_f = (np.asarray(out["topk_fairness"])[:w] if multi else None)
    return top_ws, top_idx, top_f


def _search_jax_stacked(
    sharded: Dict[str, np.ndarray],
    grids: Dict[str, StaticGrid],
    w: int,
    k: int,
    iters: int,
    n_shards: int,
    chunk_elements: int,
    banks_per_family: Tuple[int, ...],
    multi: bool = False,
):
    """ONE device program scanning every family's grid back to back."""
    from repro.core.dispatch import record_dispatch
    from repro.core.x64 import x64_context

    w_pad = sharded["baseline_ipc"].shape[0]
    names = list(grids)
    replicated = {
        f"family{fi}": _family_tables(grids[name], w_pad, k, chunk_elements)
        for fi, name in enumerate(names)
    }
    fn = _compiled_stacked_search(banks_per_family, k, iters, n_shards,
                                  multi)
    record_dispatch()
    topk_ws: Dict[str, np.ndarray] = {}
    topk_idx: Dict[str, np.ndarray] = {}
    topk_f: Dict[str, np.ndarray] = {}
    with x64_context():
        out = fn(sharded, replicated)
        for fi, name in enumerate(names):
            topk_ws[name] = np.asarray(out[f"topk_ws{fi}"])[:w]
            topk_idx[name] = np.asarray(
                out[f"topk_index{fi}"])[:w].astype(np.int64)
            if multi:
                topk_f[name] = np.asarray(out[f"topk_fairness{fi}"])[:w]
    return topk_ws, topk_idx, topk_f


# --------------------------------------------------------------------- #
# entry point
# --------------------------------------------------------------------- #

def search_static(
    workloads: Union[Sequence[Sequence[str]], AppArrays],
    families: Optional[Mapping[str, Union[FamilySpec, Mapping]]] = None,
    *,
    k: int = 1,
    backend: str = "jax",
    options: Optional[StaticOptions] = None,
    iters: int = FIG5_ITERS,
    shard: Optional[bool] = None,
    chunk_elements: int = CHUNK_ELEMENTS,
    stack_families: bool = True,
    multi_objective: bool = False,
) -> StaticSearchResult:
    """Best static (cache, bandwidth, prefetch) allocation per workload.

    Args:
      workloads: equal-size workloads — lists of app names (any n, not
        just the paper's 4) or an already-stacked ``(W, n)`` AppArrays.
      families: name -> :class:`FamilySpec` (or kwargs dict); default the
        paper's :data:`FIG5_FAMILIES`.
      k: how many best configs to return per workload (sorted, distinct).
      backend: ``"jax"`` (every family in ONE device program, workload
        axis sharded over devices) or ``"numpy"`` (the golden host
        reference, one vectorized solve per workload) — mirroring
        ``CacheController(backend=...)``.
      options: the option grid / budgets (:class:`StaticOptions`).
      iters: fixed-point iterations (Fig. 5 protocol default 40).
      shard: ``None`` auto-shards over visible devices; ``False`` forces
        single-device execution.  JAX backend only.
      chunk_elements: on-device scan chunk budget (W x chunk x n).
      stack_families: run all families back to back inside one jitted
        program (2 dispatches total, the default); ``False`` keeps the
        PR 4 one-program-per-family path (``len(families) + 1``
        dispatches) — the stacking parity reference, bit-identical per
        family.  JAX backend only.
      multi_objective: fold the Pareto front over (weighted speedup,
        min-fairness) instead of the scalar top-k — ``topk_*`` then hold
        the front's ``k`` best-ws members (ws descending, fairness
        ascending down the slots) and ``topk_fairness`` is populated;
        ``k`` doubles as the front capacity.  Min-fairness is
        ``min(speedup) / max(speedup)`` per workload.

    Returns:
      :class:`StaticSearchResult`; weighted speedups are against the
      equal-share static partitioned baseline (prefetch off), the
      ``_exhaustive_best`` normalization.
    """
    if backend not in ("numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    fams = _resolve_families(families)
    options = options or StaticOptions()

    stacked = (workloads if isinstance(workloads, AppArrays)
               else stack_mixes([list(w) for w in workloads]))
    shape = np.asarray(stacked.cpi_base).shape
    if len(shape) != 2 or shape[0] == 0:
        raise ValueError(
            f"workloads must stack to a non-empty (W, n); got {shape}")
    w, n = shape
    names = [list(m) for m in stacked.names] if stacked.names else []

    grids = {}
    for name, spec in fams.items():
        try:
            grid = family_grid(spec, n, options)
        except InfeasibleGridError as exc:
            raise InfeasibleGridError(f"family {name!r}: {exc}") from None
        if grid.n_configs == 0:
            raise InfeasibleGridError(
                f"family {name!r} has zero feasible configurations")
        grids[name] = grid
    total_units = options.cache_budget_per_app * n
    total_bw = options.bw_budget_per_app * n
    units_eq, bw_eq = equal_share(n, total_units, total_bw)
    pf_off = np.zeros(n)

    banks = {name: int(spec.bandwidth_banks) for name, spec in fams.items()}
    if backend == "numpy":
        base = memsys.evaluate(
            stacked, units_eq.astype(np.float64), bw_eq, pf_off,
            total_cache_units=total_units, total_bandwidth_gbps=total_bw,
            iters=iters).ipc
        apps_rows = [_row_apps(stacked, wi) for wi in range(w)]
        topk_ws, topk_idx, topk_f = {}, {}, {}
        for name, grid in grids.items():
            topk_ws[name], topk_idx[name], topk_f[name] = \
                _search_numpy_family(apps_rows, grid, base, k, iters,
                                     banks[name], multi_objective)
    else:
        from repro import distributed
        from repro.sim import memsys_jax

        # One shared baseline evaluation (family-independent): dispatch 1.
        base = np.asarray(memsys_jax.evaluate(
            stacked, units_eq.astype(np.float64), bw_eq, pf_off,
            total_cache_units=total_units, total_bandwidth_gbps=total_bw,
            iters=iters).ipc)

        n_shards = 1 if shard is False else distributed.row_shard_count(w)
        w_pad = -(-w // n_shards) * n_shards
        params = memsys_jax.app_params(stacked)
        sharded = {"p_" + f: np.ascontiguousarray(
            np.broadcast_to(np.asarray(v, np.float64), (w, n)))
            for f, v in params.items()}
        sharded["baseline_ipc"] = np.asarray(base, dtype=np.float64)
        if w_pad != w:
            sharded = {
                key: np.concatenate(
                    [v, np.repeat(v[-1:], w_pad - w, axis=0)])
                for key, v in sharded.items()
            }
        if stack_families:
            topk_ws, topk_idx, topk_f = _search_jax_stacked(
                sharded, grids, w, k, iters, n_shards, chunk_elements,
                tuple(banks[name] for name in grids), multi_objective)
        else:
            topk_ws, topk_idx, topk_f = {}, {}, {}
            for name, grid in grids.items():
                topk_ws[name], topk_idx[name], topk_f[name] = \
                    _search_jax_family(
                        sharded, grid, w, k, iters, n_shards,
                        chunk_elements, banks[name], multi_objective)

    return StaticSearchResult(
        family_names=list(fams),
        workloads=names,
        grids=grids,
        topk_ws=topk_ws,
        topk_index=topk_idx,
        baseline_ipc=np.asarray(base),
        backend=backend,
        k=k,
        topk_fairness=topk_f if multi_objective else None,
        multi_objective=multi_objective,
    )
