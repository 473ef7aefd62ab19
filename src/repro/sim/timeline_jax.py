"""Fused Fig. 8 timelines: ONE jitted device program for a whole manager set.

PR 2 made every timeline *segment* a device call; PR 3 removed the
per-segment host loop (one program per (manager, timeline)); this revision
removes the per-manager host loop too.  Every Table-3 manager keeps its own
segment table, the tables stack along a new leading *manager* axis (shorter
timelines pad with frozen ``NOOP`` rows), and the per-manager knob flags —
``cache_dynamic``, ``bandwidth_dynamic``, ``cache_partitioned``,
``bandwidth_partitioned``, the CPpf variant mask — become traced ``(K,)``
arrays instead of static trace constants.  A full Table-3 sweep is then
**one stacked program** (plus the shared baseline evaluation; counter:
:func:`repro.core.device_dispatches`), with no per-manager or per-segment
program.  Collecting its outputs is not one transfer: each spec's fields
are sliced out with small eager programs and fetched one by one
(:class:`PendingTimelines`), outside that counter.  The program also
returns the boundary greedy's body-application count, which
:meth:`PendingTimelines.result` adds to
:func:`repro.core.dispatch.greedy_trips`.

Stacking is exact, not approximate
    Batch rows never interact — the model, the batched Lookahead greedy,
    Algorithm-1 bandwidth partitioning and Algorithm-2 throttling are all
    row-independent — so manager k executing rows ``0..S_k-1`` of the
    stacked table reproduces its standalone fused trajectory bit-for-bit;
    rows past ``S_k`` are ``NOOP``: zero accumulation weight, no
    reconfigure flag, no controller update (``x + v*0`` and masked
    ``where`` updates are bitwise no-ops).  :func:`run_timeline` (one
    manager) is literally the K=1 case of :func:`run_timelines`, and
    ``tests/test_timeline_fused.py`` pins stacked == per-manager for every
    Table-3 manager on 1 and 8 forced host devices.

Segment tables
    :func:`segment_table` encodes a :func:`~repro.core.fig8_schedule`
    segment list as (kind, duration, reconfigure?) arrays; zero-duration
    ``reconfigure`` boundaries fold into the *following* segment's flag (a
    trailing boundary becomes a zero-duration ``NOOP`` row).
    :func:`stack_tables` right-pads the per-manager tables to the longest
    and stacks them ``(K, S)``.  Each scan step is: maybe-reconfigure
    (per-manager flag), run one interval of the model, update controller
    state elementwise by per-manager segment kind.

Controllers in the traced region
    The cache step calls the PR 2 batched greedy
    (:mod:`repro.core.cache_controller_jax`) through the masked entry
    point — non-CPpf rows pass an all-active mask, which reduces to the
    plain Lookahead exactly, and rows not reconfiguring at this step pass
    an all-inactive mask, which retires them from the greedy's while_loop
    after a single trip; bandwidth uses
    :func:`repro.core.allocate_bandwidth_jax` and prefetch
    :func:`repro.core.throttle_decision_jax`, with the ``min_allocation *
    n > total`` feasibility checks hoisted out of the traced region.
    The interval model runs through
    :func:`repro.sim.memsys_jax._evaluate_rowflags` so each manager row
    gets its own partitioned/unpartitioned regime.  The model runs under
    the named scope ``cbp.interval`` and the boundary greedy (Lookahead
    and the registry's branches) under ``cbp.greedy``
    (:mod:`repro.core.dispatch`), so a profiler trace can split the scan's
    device time between them.

Sharding
    The (manager, mix) grid is sharded across devices with
    :func:`repro.distributed.shard_grid` (2-D ``make_mesh`` +
    ``shard_map``): manager groups spread over the first mesh axis, mixes
    over the second, so different managers' timelines execute on
    different devices concurrently.  Shard counts come from
    :func:`repro.distributed.grid_shard_counts` (clamped per axis, most
    balanced factorization); both axes pad by replicating their last row
    and the padding is sliced off after the program returns, so results
    are identical on 1 and N devices.  Force N host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to test
    locally.

Parity contract: fused trajectories match the PR 2 segment-loop path (and
therefore the scalar numpy reference within its 1e-5 model tolerance) —
bit-identical controller decisions away from knife-edges, enforced by
``tests/test_timeline_fused.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import distributed
from repro.core.bandwidth_controller import (
    allocate_bandwidth_jax,
    check_bandwidth_floor,
)
from repro.core.cache_controller_jax import lookahead_masked_traced
from repro.core.coordinator import ScheduleSegment
from repro.core.dispatch import (
    GREEDY_SCOPE,
    record_dispatch,
    record_greedy_trips,
)
from repro.core.prefetch_controller import throttle_decision_jax
from repro.core.x64 import x64_context
from repro.sim import memsys_jax, policies
from repro.sim.apps import AppArrays
from repro.sim.memsys import FIXED_POINT_ITERS, FREQ_GHZ

#: Segment kinds of the fused table.  ``NOOP`` rows freeze a manager: the
#: zero-duration model evaluation never accumulates and no controller
#: fires.  They appear as the carrier of a trailing reconfigure boundary
#: (CPpf reallocates after its final interval) and as right-padding when
#: managers with shorter timelines stack against longer ones.
SAMPLE_OFF, SAMPLE_ON, RUN, NOOP = 0, 1, 2, 3

_KIND_CODES = {"sample_off": SAMPLE_OFF, "sample_on": SAMPLE_ON, "run": RUN}

#: Grid leaves that are scan CARRY state: each has exactly one output of
#: identical shape/dtype (units0 -> cache_units, bw0 -> bandwidth, pf0 ->
#: prefetch_on, active0 -> active), so a ``donate=True`` dispatch hands
#: precisely these buffers to XLA for in-place reuse — every donation is
#: consumed, none wasted (no "unusable donation" lowering warnings).
_CARRY_KEYS = ("units0", "bw0", "pf0", "active0")

#: The worker's extra output: the boundary greedy's body applications over
#: the whole scan, shaped ``(1, 1)`` per shard so that it shards like the
#: grid; never sliced per spec (:meth:`PendingTimelines.result` sums it).
_TRIPS_KEY = "greedy_trips"


def segment_table(
    schedule: Sequence[ScheduleSegment],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode a segment list as (kinds, durations_ms, reconfigure_flags).

    ``reconfigure`` boundaries are zero-duration in the schedule; folding
    each into the next segment's flag keeps the scan length equal to the
    number of *intervals actually executed* and lets one scan step be
    "maybe reconfigure, then run the segment".
    """
    rows: List[Tuple[int, float, bool]] = []
    pending = False
    for seg in schedule:
        if seg.kind == "reconfigure":
            pending = True
            continue
        rows.append((_KIND_CODES[seg.kind], seg.duration_ms, pending))
        pending = False
    if pending:
        rows.append((NOOP, 0.0, True))
    if not rows:
        raise ValueError("cannot fuse an empty schedule")
    kinds = np.array([r[0] for r in rows], dtype=np.int32)
    durations = np.array([r[1] for r in rows], dtype=np.float64)
    reconf = np.array([r[2] for r in rows], dtype=bool)
    return kinds, durations, reconf


def cppf_schedule(total_ms: float, params) -> List[ScheduleSegment]:
    """CPpf's timeline as data (mirrors ``sweep._run_cppf_batched``).

    An A/B friendliness probe at equal partitioning (excluded from the
    time-weighted mean), then per reconfiguration interval: run, then
    reallocate — including after the final interval, which is why the
    segment list *ends* with a reconfigure boundary.
    """
    p = params.prefetch_sampling_period_ms
    segments = [ScheduleSegment("sample_off", p),
                ScheduleSegment("sample_on", p)]
    t = 0.0
    while t < total_ms - 1e-9:
        dt = min(params.reconfiguration_interval_ms, total_ms - t)
        segments.append(ScheduleSegment("run", dt))
        segments.append(ScheduleSegment("reconfigure", 0.0))
        t += dt
    return segments


@dataclasses.dataclass
class TimelineSpec:
    """One manager's timeline + knobs inside a stacked program.

    ``init_units`` / ``init_bandwidth`` / ``init_prefetch`` are the
    ``(M, n)`` step-0 state; the booleans are the Table-3 mode flags that
    used to be static per-program trace constants and now ride the
    manager axis as data.

    ``cache_policy`` / ``bw_policy`` select the family's boundary
    allocator branch from the registry's ``lax.switch`` tables
    (:data:`repro.sim.policies.CACHE_POLICY_NAMES` /
    :data:`~repro.sim.policies.BW_POLICY_NAMES`; 0 = the classic
    Lookahead / Algorithm-1 pair).  ``bandwidth_banks > 1`` evaluates the
    row under the banked-token memory regime.  ``qos_bound`` /
    ``qos_gain`` parameterize the QoS branch (ignored elsewhere).
    """

    schedule: Sequence[ScheduleSegment]
    variant: str                       # "fig8" | "cppf"
    cache_dynamic: bool
    bandwidth_dynamic: bool
    cache_partitioned: bool
    bandwidth_partitioned: bool
    init_units: np.ndarray
    init_bandwidth: np.ndarray
    init_prefetch: np.ndarray
    name: str = ""
    cache_policy: int = policies.CACHE_LOOKAHEAD
    bw_policy: int = policies.BW_ALG1
    bandwidth_banks: int = 1
    qos_bound: float = policies.QOS_SLOWDOWN_BOUND
    qos_gain: float = policies.QOS_VIOLATION_GAIN

    def __post_init__(self):
        if self.variant not in ("fig8", "cppf"):
            raise ValueError(f"unknown timeline variant {self.variant!r}")
        if not 0 <= self.cache_policy < len(policies.CACHE_POLICY_NAMES):
            raise ValueError(
                f"cache_policy {self.cache_policy} has no traced branch "
                f"(table: {policies.CACHE_POLICY_NAMES})")
        if not 0 <= self.bw_policy < len(policies.BW_POLICY_NAMES):
            raise ValueError(
                f"bw_policy {self.bw_policy} has no traced branch "
                f"(table: {policies.BW_POLICY_NAMES})")
        if self.bandwidth_banks < 1:
            raise ValueError("bandwidth_banks must be >= 1")
        if (self.cache_policy or self.bw_policy) and not (
                self.cache_dynamic and self.bandwidth_dynamic):
            raise ValueError(
                "policy-branch rows must be cache_dynamic and "
                "bandwidth_dynamic (the branch fires at reconfigure "
                "boundaries gated by those flags)")
        if self.cache_policy != self.bw_policy:
            raise ValueError(
                "cache_policy and bw_policy must select the same branch: "
                "a boundary branch allocates both resources from the same "
                "signals (register a combined branch for mixed pairs)")


def stack_tables(
    tables: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
    accumulate_kinds: Sequence[Optional[int]],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stack per-manager segment tables into (K, S) arrays.

    Any order-preserving injection of a manager's rows into the unified
    slot axis is exact: batch rows never interact, and the frozen ``NOOP``
    slots between a manager's rows are bitwise no-ops for its scan state.
    This placement exploits that freedom twice:

    * shorter tables right-pad with ``NOOP`` slots (zero duration, no
      reconfigure);
    * reconfigure-carrying rows snap onto the *longest* table's
      reconfigure slots whenever the ordering allows, so the stacked
      program fires its (batch-wide) Lookahead greedy at as few slots as
      possible — e.g. the Table-3 set's non-sampling managers and CPpf
      reallocate on the same slots as the sampling managers instead of
      interleaving 1.7x more boundary steps.

    ``accumulate_kinds[k]`` restricts manager k's accumulation weight to
    one segment kind (CPpf's probe intervals are outside the measured
    window: only ``RUN`` accumulates); ``None`` accumulates every row.
    """
    lens = [len(t[0]) for t in tables]
    s_max = max(lens)
    host_reconf = np.flatnonzero(tables[int(np.argmax(lens))][2])
    K = len(tables)
    kinds = np.full((K, s_max), NOOP, dtype=np.int32)
    acc = np.zeros((K, s_max), dtype=np.float64)
    reconf = np.zeros((K, s_max), dtype=bool)
    for k, ((kk, dd, rr), only) in enumerate(zip(tables, accumulate_kinds)):
        L = len(kk)
        s = 0
        for j in range(L):
            sj = s
            if rr[j]:
                # Snap to the next shared reconfigure slot if one fits
                # before the remaining rows run out of room.
                cand = host_reconf[(host_reconf >= s)
                                   & (host_reconf <= s_max - (L - j))]
                if cand.size:
                    sj = int(cand[0])
            kinds[k, sj] = kk[j]
            acc[k, sj] = (dd[j] if only is None or kk[j] == only else 0.0)
            reconf[k, sj] = rr[j]
            s = sj + 1
    return kinds, acc, reconf


@dataclasses.dataclass
class TimelineResult:
    """Final state of one manager's fused timeline over M mixes."""

    ipc_acc: np.ndarray        # (M, n) time-weighted IPC sum
    w_acc: float               # accumulated weight (ms) — static per table
    cache_units: np.ndarray    # (M, n) int64 final allocation
    bandwidth: np.ndarray      # (M, n) final bandwidth split
    prefetch_on: np.ndarray    # (M, n) bool final prefetcher setting
    active: np.ndarray         # (M, n) bool CPpf competing mask (fig8: all)

    def mean_ipc(self) -> np.ndarray:
        return self.ipc_acc / max(self.w_acc, 1e-12)


def _make_worker(
    has_sampling: bool,
    any_cache_dynamic: bool,
    any_bandwidth_dynamic: bool,
    max_concurrent_realloc: int,
    total_units: int,
    iters: int,
    any_policy: bool = False,
    max_banks: int = 1,
):
    """Build one stacked-timeline worker for a (sub)set of managers.

    Manager knobs are *traced* ``(K,)`` arrays, so e.g. every all-static
    manager subset shares one compilation; only controller machinery no
    manager in the batch can ever reach (ATD counters without a dynamic
    cache, the delay EMA without dynamic bandwidth, the A/B sampling
    state) is statically dropped from the step.  The bucketed executor
    (:func:`_compiled_buckets`) instantiates one worker per
    segment-length bucket, which is how a bucket of fully-static managers
    sheds the sampling and ATD machinery entirely.

    ``any_policy`` (some manager uses a non-default registry branch)
    switches the boundary step to dispatch each reconfiguring manager's
    block through the registry ``lax.switch`` tables and adds the
    slowdown-reference carries the QoS branch consumes; ``max_banks``
    is the static bank-axis width of the banked-token model (1 = flat).
    Both default off, so every pre-registry call site compiles the exact
    program it used to.
    """
    f64 = jnp.float64
    total_cache_f = float(total_units)

    def worker(grid, mgr, replicated):
        # The whole scan runs in FLATTENED (K*M, ...) row form: XLA CPU's
        # codegen for the model's axis(-1) reductions is bit-stable across
        # 2-D row counts but not across 3-D leading shapes, and the
        # stacked-vs-per-manager bit-parity contract rides on that
        # (``tests/test_timeline_fused.py``).  The (K, M) structure only
        # reappears on the outputs so shard_map can split both mesh axes.
        K, M, n = grid["p_cpi_base"].shape
        B = K * M

        def rows(a):
            return a.reshape((B,) + a.shape[2:])

        p = {k: rows(grid["p_" + k])
             for k in memsys_jax.PARAM_FIELDS}       # (B, n)
        min_ways = rows(grid["min_ways"])            # (B,) int32
        thr = rows(grid["speedup_threshold"])        # (B, 1)
        min_bw = rows(grid["min_bandwidth_allocation"])
        atd_decay = rows(grid["atd_decay"])          # (B, 1, 1)
        bw_decay = rows(grid["bandwidth_delay_decay"])
        total_bw = replicated["total_bandwidth"]
        llc_extra = replicated["llc_extra_cycles"]

        # Per-manager knob flags expanded to per-row (B, 1) masks.
        def per_row(flag):
            return jnp.repeat(flag, M)[:, None]

        cache_dyn_k = mgr["cache_dynamic"]                 # (K,)
        bw_dyn = per_row(mgr["bandwidth_dynamic"])
        cache_part = per_row(mgr["cache_partitioned"])
        bw_part = per_row(mgr["bandwidth_partitioned"])
        is_cppf = per_row(mgr["is_cppf"])
        if any_policy:
            cache_pol_k = mgr["cache_policy"]              # (K,) int32
            qos_bound = per_row(mgr["qos_bound"])          # (B, 1)
            qos_gain = per_row(mgr["qos_gain"])
        banks_row = (per_row(mgr["bandwidth_banks"])
                     if max_banks > 1 else None)           # (B, 1) f64

        if any_cache_dynamic:
            # The ATD is a LINEAR functional of the per-step hit curves,
            # and the hit curves take only two values per client over the
            # whole timeline (prefetch on / off — ``pf`` is always exactly
            # 0.0 or 1.0).  So instead of accumulating a (B, n, U+1) ATD
            # grid every step, the scan carries two (B, n) weight
            # accumulators — the decayed kilo-instruction mass observed
            # with the prefetcher off resp. on — and the full ATD grid
            # ``hits_off * w_off + hits_on * w_on`` materializes only at
            # reconfigure boundaries, right where the Lookahead greedy
            # consumes it.  The exp-heavy ``mpki_curve`` grids precompute
            # once per program.  (The per-step accumulation used to be
            # ~70% of a Table-3 sweep's wall time.)
            u_pts = jnp.arange(total_units + 1, dtype=f64)
            pc = {k: v[..., :, None] for k, v in p.items()}  # (B, n, 1)

            def hits_for(pf_const):
                units_g = u_pts - pc["pf_pollution"] * pf_const
                m_g = memsys_jax.mpki_curve(pc, units_g)
                eff_miss = m_g * (1.0 - pc["pf_cov"] * pf_const)
                return jnp.maximum(pc["apki"] - eff_miss, 0.0)

            hits_off = hits_for(jnp.asarray(0.0, f64))
            hits_on = hits_for(jnp.asarray(1.0, f64))

        def reconfigure(operand):
            """Boundary step: cache -> bandwidth (paper priority order).

            Cache reallocation gathers every reconfiguring manager's M-row
            block (traced ``dynamic_slice``; up to the static
            ``max_concurrent_realloc`` bound), materializes their ATD
            grids from the two weight coefficients, and runs ONE
            concatenated ``(G*M, n)`` masked greedy instead of G
            sequential mini-greedies: the while_loop pays the *max* trip
            count over the blocks, not the sum — on CPU the trips are
            tiny-op bound, so batching the boundary refresh is the big
            win.  Exact because the greedy is row-independent and its only
            float reductions are max/argmax (order-insensitive), so
            results are bit-invariant to the batch row count — unlike the
            model eval, which is why the scan itself stays flattened 2-D.
            Slot alignment (:func:`stack_tables`) keeps the number of
            boundary slots minimal; managers not reallocating here are
            untouched.
            """
            if any_policy:
                (units, bw, w_off, w_on, bw_acc, active, do_r, realloc_k,
                 ref_ipc, prev_ipc) = operand
            else:
                units, bw, w_off, w_on, bw_acc, active, do_r, realloc_k \
                    = operand
            trips = jnp.int32(0)
            if any_bandwidth_dynamic:
                # Algorithm-1 bandwidth update first: it reads none of the
                # cache state, and running it before the cache gather lets
                # the registry branches below see the post-update array —
                # identity rows keep it bit-for-bit, policy rows override
                # their own block from the same boundary signals.
                bw = jnp.where(do_r & bw_dyn,
                               allocate_bandwidth_jax(bw_acc, total_bw,
                                                      min_bw),
                               bw)
            # Under manager-axis sharding the global concurrency bound
            # can exceed this shard's manager count — clamp.
            G = min(max_concurrent_realloc, K)
            if any_cache_dynamic and G > 0:
                # Reallocating managers first (ascending index, stable) —
                # real managers outrank any K-padding duplicates.
                order = jnp.argsort(~realloc_k, stable=True)
                min32 = min_ways.astype(jnp.int32)

                def blk(a, off):
                    return jax.lax.dynamic_slice_in_dim(a, off, M, axis=0)

                offs = [order[g] * M for g in range(G)]
                valids = [realloc_k[order[g]] for g in range(G)]
                # An all-inactive mask (non-CPpf rows pass all-active,
                # which reduces to the plain Lookahead; invalid sentinel
                # blocks retire after one trip).
                act_all = jnp.concatenate(
                    [blk(active, offs[g]) & valids[g] for g in range(G)],
                    axis=0)
                atd_all = jnp.concatenate(
                    [blk(hits_off, offs[g])
                     * blk(w_off, offs[g])[..., :, None]
                     + blk(hits_on, offs[g])
                     * blk(w_on, offs[g])[..., :, None]
                     for g in range(G)], axis=0)
                min_all = jnp.concatenate(
                    [blk(min32, offs[g]) for g in range(G)], axis=0)
                with jax.named_scope(GREEDY_SCOPE):
                    fresh, trips = lookahead_masked_traced(
                        atd_all, min_all, act_all, total_units)
                if any_policy:
                    # Registry dispatch: each reconfiguring manager's block
                    # goes through its family's boundary branch.  Branch 0
                    # returns the Lookahead slice + the (post-Algorithm-1)
                    # bandwidth slice untouched, so classic managers stay
                    # bit-identical; the auction/QoS branches compute both
                    # resources from the same boundary signals (ATD grid,
                    # delay EMA, and the slowdown vs the first-interval
                    # reference the scan carries for the QoS constraint).
                    slow = jnp.where(
                        prev_ipc > 0,
                        ref_ipc / jnp.where(prev_ipc > 0, prev_ipc, 1.0),
                        1.0)

                    def _classic_branch(op):
                        return op[0], op[1]

                    def _auction_branch(op):
                        look_b, bw_b, atd_b, min_b, acc_b, floor_b, \
                            slow_b, qb, qg = op
                        return policies.auction_allocate_jax(
                            atd_b, acc_b, min_ways=min_b,
                            total_units=total_units,
                            min_bandwidth=floor_b,
                            total_bandwidth=total_bw)

                    def _qos_branch(op):
                        look_b, bw_b, atd_b, min_b, acc_b, floor_b, \
                            slow_b, qb, qg = op
                        return policies.qos_allocate_jax(
                            atd_b, acc_b, slow_b, min_ways=min_b,
                            total_units=total_units,
                            min_bandwidth=floor_b,
                            total_bandwidth=total_bw,
                            bound=qb, gain=qg)

                    branches = [_classic_branch, _auction_branch,
                                _qos_branch]
                for g in range(G):
                    units_b = fresh[g * M:(g + 1) * M].astype(units.dtype)
                    if any_policy:
                        bw_b = blk(bw, offs[g])
                        op_g = (units_b, bw_b,
                                atd_all[g * M:(g + 1) * M],
                                blk(min32, offs[g])[:, None],
                                blk(bw_acc, offs[g]),
                                blk(min_bw, offs[g]),
                                blk(slow, offs[g]),
                                blk(qos_bound, offs[g]),
                                blk(qos_gain, offs[g]))
                        with jax.named_scope(GREEDY_SCOPE):
                            units_b, bw_new_b = jax.lax.switch(
                                cache_pol_k[order[g]], branches, op_g)
                        new_bw_b = jnp.where(
                            valids[g] & blk(bw_dyn, offs[g]),
                            bw_new_b, bw_b)
                        bw = jax.lax.dynamic_update_slice_in_dim(
                            bw, new_bw_b, offs[g], axis=0)
                    old_b = blk(units, offs[g])
                    new_b = jnp.where(valids[g], units_b, old_b)
                    units = jax.lax.dynamic_update_slice_in_dim(
                        units, new_b, offs[g], axis=0)
            if any_cache_dynamic:
                # The boundary ATD decay is a scalar multiply of the whole
                # grid, i.e. of both weight coefficients.
                decay_w = atd_decay[..., 0]                    # (B, 1)
                w_off = jnp.where(do_r, w_off * decay_w, w_off)
                w_on = jnp.where(do_r, w_on * decay_w, w_on)
            return units, bw, w_off, w_on, trips

        def step(carry_trips, seg):
            carry, trips = carry_trips
            kind_k, acc_k, reconf_k = seg                      # (K,) each
            if any_policy:
                (units, bw, pf, active, w_off, w_on, bw_acc, ipc_acc,
                 off_ipc, ref_ipc, prev_ipc) = carry
            else:
                (units, bw, pf, active, w_off, w_on, bw_acc, ipc_acc,
                 off_ipc) = carry
            kind = jnp.repeat(kind_k, M)[:, None]              # (B, 1)
            acc_dt = jnp.repeat(acc_k, M)[:, None]
            do_r = jnp.repeat(reconf_k, M)[:, None]
            operand = (units, bw, w_off, w_on, bw_acc, active, do_r,
                       reconf_k & cache_dyn_k)
            if any_policy:
                operand = operand + (ref_ipc, prev_ipc)
            units, bw, w_off, w_on, boundary_trips = jax.lax.cond(
                jnp.any(reconf_k), reconfigure,
                lambda op: (op[0], op[1], op[2], op[3], jnp.int32(0)),
                operand)
            trips = trips + boundary_trips

            # The A/B samples force the prefetcher off/on for everyone;
            # other segments run the current per-client setting.
            if has_sampling:
                pf_f = jnp.where(kind == SAMPLE_OFF, 0.0,
                                 jnp.where(kind == SAMPLE_ON, 1.0,
                                           pf.astype(f64)))
            else:
                pf_f = pf.astype(f64)
            out = memsys_jax._evaluate_rowflags(
                p, units.astype(f64), bw, pf_f,
                jnp.asarray(total_cache_f, f64), total_bw, llc_extra,
                cache_part, bw_part, iters=iters,
                bandwidth_banks=banks_row, max_banks=max_banks)
            ipc, q_ns = out[0], out[1]
            if any_policy:
                # Slowdown signal for the QoS branch: the reference is
                # each row's FIRST executed segment (the equal-share
                # initial state — reconfigures fold onto the following
                # segment, so the first run always precedes any boundary),
                # the denominator its most recent one.  Frozen NOOP slots
                # update neither.
                executed = kind != NOOP
                ref_ipc = jnp.where((ref_ipc == 0.0) & executed,
                                    ipc, ref_ipc)
                prev_ipc = jnp.where(executed, ipc, prev_ipc)

            # Accumulation weights come from the stacked table: fig8
            # accumulates every executed segment (samples included),
            # CPpf's probe intervals and all NOOP rows carry weight 0 —
            # a bitwise no-op on the accumulators.
            if any_cache_dynamic:
                kappa = (ipc * FREQ_GHZ * 1e6
                         * jnp.asarray(1.0, f64) / 1000.0) * acc_dt
                on_mask = pf_f == 1.0
                w_on = w_on + jnp.where(on_mask, kappa, 0.0)
                w_off = w_off + jnp.where(on_mask, 0.0, kappa)
            ipc_acc = ipc_acc + ipc * acc_dt
            if any_bandwidth_dynamic:
                # The delay EMA advances once per *executed* segment of
                # the manager's own table — frozen NOOP rows must not
                # decay it, so the update is gated, not weight-folded.
                executes = (kind != NOOP) & bw_dyn
                bw_acc = jnp.where(executes,
                                   bw_decay * bw_acc + q_ns * acc_dt,
                                   bw_acc)

            if has_sampling:
                decision = throttle_decision_jax(ipc, off_ipc, thr)
                sample_on = kind == SAMPLE_ON
                active = jnp.where(sample_on & is_cppf, ~decision, active)
                pf = jnp.where(sample_on & ~is_cppf, decision, pf)
                off_ipc = jnp.where(kind == SAMPLE_OFF, ipc, off_ipc)
            new_carry = (units, bw, pf, active, w_off, w_on, bw_acc,
                         ipc_acc, off_ipc)
            if any_policy:
                new_carry = new_carry + (ref_ipc, prev_ipc)
            return (new_carry, trips), None

        zeros = jnp.zeros((B, n), dtype=f64)
        carry0 = (rows(grid["units0"]), rows(grid["bw0"]),
                  rows(grid["pf0"]), rows(grid["active0"]),
                  zeros, zeros, zeros, zeros, zeros)
        if any_policy:
            carry0 = carry0 + (zeros, zeros)
        xs = (mgr["kinds"].T, mgr["acc"].T, mgr["reconf"].T)   # (S, K)
        (carry, trips), _ = jax.lax.scan(step, (carry0, jnp.int32(0)), xs)
        units, bw, pf, active, _woff, _won, _bw_acc, ipc_acc, _off \
            = carry[:9]
        out = {k: v.reshape(K, M, n) for k, v in
               {"ipc_acc": ipc_acc, "cache_units": units, "bandwidth": bw,
                "prefetch_on": pf, "active": active}.items()}
        out[_TRIPS_KEY] = trips.reshape(1, 1)
        return out

    return worker


@functools.lru_cache(maxsize=None)
def _compiled_stacked(
    has_sampling: bool,
    any_cache_dynamic: bool,
    any_bandwidth_dynamic: bool,
    max_concurrent_realloc: int,
    total_units: int,
    iters: int,
    grid_shards: Tuple[int, int],
    donate: bool = False,
    any_policy: bool = False,
    max_banks: int = 1,
):
    """Build the jitted (optionally shard_mapped) stacked-timeline executor.

    Cached per static configuration so repeated sweeps reuse both the
    Python wrapper and XLA's compilation cache; jit retraces on new array
    shapes (different K, M, n or segment count) as usual.  ``donate=True``
    compiles with the ``_CARRY_KEYS`` grid leaves split into a donated
    first argument: the chunk's carry-state buffers are reused in place
    for the outputs, so a streaming caller does not hold two chunks'
    worth of carry buffers live at once (the PR 8 leftover in ROADMAP
    item 3).
    """
    worker = _make_worker(has_sampling, any_cache_dynamic,
                          any_bandwidth_dynamic, max_concurrent_realloc,
                          total_units, iters, any_policy, max_banks)
    if grid_shards != (1, 1):
        worker = distributed.shard_grid(worker, grid_shards)
    if not donate:
        return jax.jit(worker)

    def donating(carry0, grid_rest, mgr, replicated):
        return worker({**grid_rest, **carry0}, mgr, replicated)

    return jax.jit(donating, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _compiled_buckets(
    bucket_statics: Tuple[Tuple[bool, bool, bool, int, bool, int], ...],
    total_units: int,
    iters: int,
    mix_shards: int,
    donate: bool = False,
):
    """Build the jitted multi-bucket stacked executor: one worker per
    segment-length bucket, all inside ONE jitted program (one dispatch).

    Frozen-row skipping: a manager bucketed with peers of similar table
    length scans only ~its own slot count instead of the whole set's
    ``s_max``, and each bucket's worker drops the controller machinery its
    managers never reach.  Every bucket still runs the flattened 2-D
    ``(K_g * M, n)`` row scan, so the stacked-vs-fused bit-parity contract
    is untouched.

    Sharding: bucket programs may only split the MIX axis — all buckets
    must then address the SAME device subset (jit rejects shard_maps over
    different device sets in one program), which a shared ``(1,
    mix_shards)`` mesh guarantees.  Manager-axis sharding keeps the
    single-bucket path (:func:`_compiled_stacked`).
    """
    workers = []
    for (has_sampling, cache_dyn, bw_dyn, max_realloc, any_policy,
         max_banks) in bucket_statics:
        w = _make_worker(has_sampling, cache_dyn, bw_dyn, max_realloc,
                         total_units, iters, any_policy, max_banks)
        if mix_shards > 1:
            w = distributed.shard_grid(w, (1, mix_shards))
        workers.append(w)

    def fn(bucket_grids, bucket_mgrs, replicated):
        return tuple(
            w(g, m, replicated)
            for w, g, m in zip(workers, bucket_grids, bucket_mgrs))

    if not donate:
        return jax.jit(fn)

    def donating(bucket_carries, bucket_rests, bucket_mgrs, replicated):
        grids = tuple({**g, **c}
                      for g, c in zip(bucket_rests, bucket_carries))
        return fn(grids, bucket_mgrs, replicated)

    return jax.jit(donating, donate_argnums=(0,))


def _length_buckets(lens: Sequence[int]) -> List[List[int]]:
    """Group manager indices for the bucketed stacked scan.

    Managers share a bucket exactly when their segment-table lengths are
    equal: equal lengths mean zero frozen ``NOOP`` rows inside a bucket,
    and same-length Table-3 tables share their reconfigure slots, so
    bucket-mates' boundary refreshes merge into ONE concatenated greedy
    whose while_loop cost is sublinear in the row count.  (Two rejected
    rules, both measured against per-manager fused on warm wall time:
    merge-within-2x-length traded frozen rows for fewer buckets and
    consistently lost; splitting further by the (sampling, cache_dynamic,
    bandwidth_dynamic) statics triple un-merged those boundary greedies
    and gave back ~1% — the per-slot machinery a non-dynamic manager
    over-pays inside a mixed bucket is masked ``(B, n)`` arithmetic,
    cheaper than a separate bucket's serial while trips.  All buckets
    run inside ONE device program, so bucket count is free at dispatch
    level.)  Stable: equal lengths keep spec order.
    """
    order = sorted(range(len(lens)), key=lambda i: (lens[i], i))
    buckets: List[List[int]] = []
    for i in order:
        if buckets and lens[i] == lens[buckets[-1][0]]:
            buckets[-1].append(i)
        else:
            buckets.append([i])
    return buckets


def _per_row(value, shape: Tuple[int, ...], dtype) -> np.ndarray:
    """Materialize a scalar-or-per-row tunable at its full batch shape.

    Per-row tunables must carry the leading (manager, mix) axes explicitly
    so ``shard_map`` can split them alongside the model state.
    """
    arr = np.asarray(value, dtype=dtype)
    # Scalars and per-mix arrays gain trailing singletons, then broadcast
    # along the leading manager axis (the tunables are manager-shared).
    arr = arr.reshape(arr.shape + (1,) * (len(shape) - 1 - arr.ndim))
    return np.ascontiguousarray(np.broadcast_to(arr, shape))


def _pad_axis(tree: dict, axis: int, target: int) -> dict:
    """Right-pad every leaf's ``axis`` to ``target`` rows by replication."""
    out = {}
    for key, v in tree.items():
        cur = v.shape[axis]
        if cur == target:
            out[key] = v
            continue
        idx = (slice(None),) * axis
        last = v[idx + (slice(cur - 1, cur),)]
        reps = np.repeat(last, target - cur, axis=axis)
        out[key] = np.concatenate([v, reps], axis=axis)
    return out


@dataclasses.dataclass
class PendingTimelines:
    """An in-flight stacked-timeline dispatch (asynchronous handle).

    The device program is already enqueued when this object exists;
    ``outputs`` holds its per-worker dicts of *device* arrays (one worker
    per length bucket).  Nothing blocks until :meth:`result` performs the
    device->host transfer, so a caller can overlap host work (generating
    the next chunk of a stream) with the device computing this one — the
    double-buffering contract of :mod:`repro.sim.stream_sweep`.

    ``device_results`` are the per-spec ``{field: (M, n)}`` device arrays,
    sliced from ``outputs`` on first use: each slice is a small eager
    device program of its own, outside the dispatch counter.

    ``donated_inputs`` (``donate=True`` dispatches only) are the device
    handles of the grid buffers handed to XLA: after the dispatch they are
    consumed (``is_deleted()``), the proof the streaming caller is not
    holding chunk c's grid alive while chunk c+1 transfers.
    """

    outputs: Tuple[dict, ...]
    split: Callable[[Tuple[dict, ...]], List[dict]]
    w_accs: List[float]
    donated_inputs: Optional[List] = None

    @functools.cached_property
    def device_results(self) -> List[dict]:
        # Sliced inside the x64 context: slicing a sharded float64 result
        # is itself a traced program and must lower at the same precision
        # the stacked program produced.
        with x64_context():
            return self.split(self.outputs)

    def block_until_ready(self) -> "PendingTimelines":
        jax.block_until_ready(self.outputs)
        return self

    def result(self) -> List[TimelineResult]:
        """Blocking device->host transfer into :class:`TimelineResult`s;
        adds the programs' greedy trips to
        :func:`repro.core.dispatch.greedy_trips`."""
        out = []
        for w_acc, dev in zip(self.w_accs, self.device_results):
            host = {k: np.asarray(v) for k, v in dev.items()}
            out.append(TimelineResult(
                ipc_acc=host["ipc_acc"],
                w_acc=w_acc,
                cache_units=host["cache_units"].astype(np.int64),
                bandwidth=host["bandwidth"],
                prefetch_on=host["prefetch_on"],
                active=host["active"],
            ))
        # Fetched last: the slices above are enqueued behind the program
        # while it runs, and fetching any of its outputs first would make
        # the host wait for it before enqueuing them.
        trips = jax.device_get([o[_TRIPS_KEY] for o in self.outputs])
        record_greedy_trips(sum(int(t.sum()) for t in trips))
        return out


@dataclasses.dataclass
class StagedTimelines:
    """A stacked-timeline program with every input built on the host and
    not yet dispatched (:func:`stage_timelines`).  :meth:`dispatch`
    enqueues it: one counted device program."""

    fn: Callable
    args: tuple                     # the program's non-donated arguments
    carry: Optional[Any]            # host carry leaves to donate, or None
    split: Callable[[Tuple[dict, ...]], List[dict]]
    w_accs: List[float]

    def dispatch(self) -> PendingTimelines:
        record_dispatch()
        donated = None
        with x64_context():
            if self.carry is not None:
                # Stable device identities for the donated carry buffers:
                # transfer first, keep the handles, and hand exactly those
                # buffers to the program.  They are consumed by the dispatch
                # (``is_deleted()`` afterwards) — the streaming smoke's gate.
                carry = jax.device_put(self.carry)
                donated = jax.tree_util.tree_leaves(carry)
                out = self.fn(carry, *self.args)
            else:
                out = self.fn(*self.args)
        outputs = out if isinstance(out, tuple) else (out,)
        return PendingTimelines(outputs, self.split, self.w_accs, donated)


def run_timelines(
    apps: Union[AppArrays, dict],
    specs: Sequence[TimelineSpec],
    *,
    total_units: int,
    total_bandwidth: float,
    llc_extra_cycles: float = 0.0,
    min_ways=4,
    speedup_threshold=1.05,
    min_bandwidth_allocation=1.0,
    atd_decay=0.5,
    bandwidth_delay_decay=0.5,
    iters: int = FIXED_POINT_ITERS,
    shard: Optional[bool] = None,
    donate: bool = False,
) -> List[TimelineResult]:
    """Execute a whole manager set's timelines as ONE device program.

    Args:
      apps: mix-stacked application profiles, every field ``(M, n)``.
      specs: one :class:`TimelineSpec` per manager — each keeps its own
        segment list and Table-3 knob flags; the tables stack along the
        leading manager axis (see :func:`stack_tables`).
      min_ways / speedup_threshold / min_bandwidth_allocation / atd_decay /
        bandwidth_delay_decay: scalars or per-mix arrays (``param_grid``),
        shared by every manager in the batch — exactly how ``run_sweep``
        applies one ``CBPParams`` across the Table-3 set.
      shard: ``None`` auto-shards the (manager, mix) grid over all visible
        devices (:func:`repro.distributed.grid_shard_counts`, padding both
        axes as needed); ``False`` forces single-device execution.

    Returns:
      One :class:`TimelineResult` of host arrays per spec.
    """
    return run_timelines_async(
        apps, specs,
        total_units=total_units,
        total_bandwidth=total_bandwidth,
        llc_extra_cycles=llc_extra_cycles,
        min_ways=min_ways,
        speedup_threshold=speedup_threshold,
        min_bandwidth_allocation=min_bandwidth_allocation,
        atd_decay=atd_decay,
        bandwidth_delay_decay=bandwidth_delay_decay,
        iters=iters,
        shard=shard,
        donate=donate,
    ).result()


def run_timelines_async(
    apps: Union[AppArrays, dict],
    specs: Sequence[TimelineSpec],
    **kwargs,
) -> PendingTimelines:
    """:func:`run_timelines` without the blocking device->host transfer.

    Dispatches the stacked program(s) and returns a
    :class:`PendingTimelines` handle holding device arrays; call
    ``.result()`` for the host-side :class:`TimelineResult`s.  Argument
    semantics are identical to :func:`run_timelines` (which is literally
    this followed by ``.result()``); it is :func:`stage_timelines`
    followed by :meth:`StagedTimelines.dispatch`.

    ``donate=True`` transfers the carry-state grid leaves (``units0`` /
    ``bw0`` / ``pf0`` / ``active0``) to the device first and donates
    exactly those buffers to the program — each aliases the final-state
    output of identical shape/dtype, so a chunked stream
    (:mod:`repro.sim.stream_sweep`) reuses chunk c's carry buffers for
    chunk c's outputs instead of allocating fresh ones.  Donation changes
    buffer *lifetime* only — results are bit-identical to the non-donated
    path and the dispatch count is unchanged.
    """
    return stage_timelines(apps, specs, **kwargs).dispatch()


def stage_timelines(
    apps: Union[AppArrays, dict],
    specs: Sequence[TimelineSpec],
    *,
    total_units: int,
    total_bandwidth: float,
    llc_extra_cycles: float = 0.0,
    min_ways=4,
    speedup_threshold=1.05,
    min_bandwidth_allocation=1.0,
    atd_decay=0.5,
    bandwidth_delay_decay=0.5,
    iters: int = FIXED_POINT_ITERS,
    shard: Optional[bool] = None,
    donate: bool = False,
) -> StagedTimelines:
    """Everything :func:`run_timelines_async` does on the host before the
    device call: feasibility checks, segment tables, the stacked grid and
    the compiled program.  Arguments as in :func:`run_timelines`."""
    if not specs:
        raise ValueError("need at least one TimelineSpec")
    params = memsys_jax.app_params(apps)
    shape = np.asarray(params["cpi_base"]).shape
    if len(shape) != 2:
        raise ValueError(f"apps must be mix-stacked (M, n); got {shape}")
    M, n = shape
    K = len(specs)

    # Feasibility checks hoisted out of the traced region (the numpy
    # controllers validate per call; the fused program validates once).
    if any(s.bandwidth_dynamic for s in specs):
        check_bandwidth_floor(min_bandwidth_allocation, n, total_bandwidth)
    if any(s.cache_dynamic for s in specs) and np.any(
            np.asarray(min_ways, dtype=np.int64) * n > total_units):
        raise ValueError("min_ways * n exceeds capacity")

    tables = [segment_table(s.schedule) for s in specs]
    accum = [RUN if s.variant == "cppf" else None for s in specs]

    grid = {"p_" + k: np.ascontiguousarray(
        np.broadcast_to(np.asarray(v, np.float64), (K, M, n)))
        for k, v in params.items()}
    grid.update(
        units0=np.stack([np.broadcast_to(
            np.asarray(s.init_units, dtype=np.int32), (M, n))
            for s in specs]),
        bw0=np.stack([np.broadcast_to(
            np.asarray(s.init_bandwidth, dtype=np.float64), (M, n))
            for s in specs]),
        pf0=np.stack([np.broadcast_to(
            np.asarray(s.init_prefetch, dtype=bool), (M, n))
            for s in specs]),
        active0=np.ones((K, M, n), dtype=bool),
        min_ways=_per_row(min_ways, (K, M), np.int32),
        speedup_threshold=_per_row(speedup_threshold, (K, M, 1), np.float64),
        min_bandwidth_allocation=_per_row(
            min_bandwidth_allocation, (K, M, 1), np.float64),
        atd_decay=_per_row(atd_decay, (K, M, 1, 1), np.float64),
        bandwidth_delay_decay=_per_row(
            bandwidth_delay_decay, (K, M, 1), np.float64),
    )
    flags = {
        "cache_dynamic": np.array([s.cache_dynamic for s in specs]),
        "bandwidth_dynamic": np.array(
            [s.bandwidth_dynamic for s in specs]),
        "cache_partitioned": np.array(
            [s.cache_partitioned for s in specs]),
        "bandwidth_partitioned": np.array(
            [s.bandwidth_partitioned for s in specs]),
        "is_cppf": np.array([s.variant == "cppf" for s in specs]),
        "cache_policy": np.array(
            [s.cache_policy for s in specs], dtype=np.int32),
        "qos_bound": np.array(
            [s.qos_bound for s in specs], dtype=np.float64),
        "qos_gain": np.array(
            [s.qos_gain for s in specs], dtype=np.float64),
        "bandwidth_banks": np.array(
            [float(s.bandwidth_banks) for s in specs], dtype=np.float64),
    }
    replicated = {
        "total_bandwidth": np.float64(total_bandwidth),
        "llc_extra_cycles": np.float64(llc_extra_cycles),
    }

    grid_shards = ((1, 1) if shard is False
                   else distributed.grid_shard_counts(K, M))
    # Donation is the single-host streaming optimization: under sharding
    # the committed carry buffers would be resharded before use and the
    # donation wasted (XLA cannot alias across shardings), so it degrades
    # to the plain path there.
    donate = donate and grid_shards == (1, 1)
    buckets = _length_buckets([len(t[0]) for t in tables])
    if grid_shards[0] == 1 and len(buckets) > 1:
        # Frozen-row-skipping path: short-table managers stop paying for
        # every slot of the longest table.  Only the mix axis may shard
        # here (all buckets then share one mesh over one device subset);
        # a sharded manager axis takes the single-bucket path below.
        return _stage_buckets(
            buckets, tables, accum, grid, flags, replicated,
            K, M, grid_shards[1], int(total_units), int(iters), donate)
    kinds, acc, reconf = stack_tables(
        [tables[i] for i in range(K)], accum)
    mgr = {"kinds": kinds, "acc": acc, "reconf": reconf, **flags}
    k_pad = -(-K // grid_shards[0]) * grid_shards[0]
    m_pad = -(-M // grid_shards[1]) * grid_shards[1]
    # Pad with copies of the last manager/mix row; sliced off after
    # the program (padding rows are duplicates, never feed real rows).
    grid = _pad_axis(_pad_axis(grid, 1, m_pad), 0, k_pad)
    mgr = _pad_axis(mgr, 0, k_pad)

    has_sampling = bool(np.isin(kinds, (SAMPLE_OFF, SAMPLE_ON)).any())
    # The most cache-dynamic managers that ever reallocate on the same
    # slot — the static bound on mini-greedies per boundary step.
    cache_dyn_col = flags["cache_dynamic"][:, None]
    max_realloc = int(
        (reconf & cache_dyn_col).sum(axis=0).max(initial=0))
    fn = _compiled_stacked(
        has_sampling,
        any(s.cache_dynamic for s in specs),
        any(s.bandwidth_dynamic for s in specs),
        max_realloc, int(total_units), int(iters), grid_shards, donate,
        any(s.cache_policy or s.bw_policy for s in specs),
        max(s.bandwidth_banks for s in specs))
    carry = ({k: grid.pop(k) for k in _CARRY_KEYS} if donate else None)

    def split(outs):
        # Per-spec device-side slices: no transfer, no block — padding
        # rows fall away exactly as a host-side [:K, :M] slice would.
        (res,) = outs
        return [{f: v[k, :M] for f, v in res.items() if f != _TRIPS_KEY}
                for k in range(K)]

    return StagedTimelines(fn, (grid, mgr, replicated), carry, split,
                           [float(a.sum()) for a in acc])


def _stage_buckets(buckets, tables, accum, grid, flags, replicated,
                   K: int, M: int, mix_shards: int,
                   total_units: int, iters: int,
                   donate: bool = False) -> StagedTimelines:
    """Stage the stacked set as per-length bucket scans in ONE program.

    Each bucket stacks only its own tables (:func:`stack_tables` snaps
    reconfigure slots within the bucket) and carries its own static knob
    summary, so e.g. the fully-static bucket drops the ATD precompute and
    sampling machinery outright.  The staged program's per-spec device
    slices restore spec order.
    """
    m_pad = -(-M // mix_shards) * mix_shards
    statics = []
    bucket_grids = []
    bucket_mgrs = []
    w_accs = {}
    for idx_g in buckets:
        sel = np.asarray(idx_g)
        kinds_g, acc_g, reconf_g = stack_tables(
            [tables[i] for i in idx_g], [accum[i] for i in idx_g])
        for row, i in enumerate(idx_g):
            w_accs[i] = float(acc_g[row].sum())
        mgr_g = {"kinds": kinds_g, "acc": acc_g, "reconf": reconf_g,
                 **{k: v[sel] for k, v in flags.items()}}
        grid_g = _pad_axis({k: v[sel] for k, v in grid.items()}, 1, m_pad)
        cache_dyn_col = mgr_g["cache_dynamic"][:, None]
        statics.append((
            bool(np.isin(kinds_g, (SAMPLE_OFF, SAMPLE_ON)).any()),
            bool(mgr_g["cache_dynamic"].any()),
            bool(mgr_g["bandwidth_dynamic"].any()),
            int((reconf_g & cache_dyn_col).sum(axis=0).max(initial=0)),
            bool(mgr_g["cache_policy"].any()),
            int(mgr_g["bandwidth_banks"].max(initial=1)),
        ))
        bucket_grids.append(grid_g)
        bucket_mgrs.append(mgr_g)

    fn = _compiled_buckets(tuple(statics), total_units, iters, mix_shards,
                           donate)
    carry = (tuple({k: g.pop(k) for k in _CARRY_KEYS} for g in bucket_grids)
             if donate else None)

    def split(outs):
        device_results: List[Optional[dict]] = [None] * K
        for idx_g, o in zip(buckets, outs):
            for row, i in enumerate(idx_g):
                device_results[i] = {k: v[row, :M] for k, v in o.items()
                                     if k != _TRIPS_KEY}
        return device_results

    return StagedTimelines(
        fn, (tuple(bucket_grids), tuple(bucket_mgrs), replicated), carry,
        split, [w_accs[i] for i in range(K)])


def run_timeline(
    apps: Union[AppArrays, dict],
    schedule: Sequence[ScheduleSegment],
    *,
    variant: str = "fig8",
    init_units: np.ndarray,
    init_bandwidth: np.ndarray,
    init_prefetch: np.ndarray,
    cache_dynamic: bool,
    bandwidth_dynamic: bool,
    cache_partitioned: bool,
    bandwidth_partitioned: bool,
    total_units: int,
    total_bandwidth: float,
    llc_extra_cycles: float = 0.0,
    min_ways=4,
    speedup_threshold=1.05,
    min_bandwidth_allocation=1.0,
    atd_decay=0.5,
    bandwidth_delay_decay=0.5,
    iters: int = FIXED_POINT_ITERS,
    shard: Optional[bool] = None,
) -> TimelineResult:
    """Execute one manager's whole timeline as ONE device program.

    The K=1 case of :func:`run_timelines` — the per-manager fused path the
    stacked sweep is parity-pinned against.  See ``run_timelines`` for
    argument semantics.
    """
    spec = TimelineSpec(
        schedule=schedule,
        variant=variant,
        cache_dynamic=bool(cache_dynamic),
        bandwidth_dynamic=bool(bandwidth_dynamic),
        cache_partitioned=bool(cache_partitioned),
        bandwidth_partitioned=bool(bandwidth_partitioned),
        init_units=init_units,
        init_bandwidth=init_bandwidth,
        init_prefetch=init_prefetch,
    )
    return run_timelines(
        apps, [spec],
        total_units=total_units,
        total_bandwidth=total_bandwidth,
        llc_extra_cycles=llc_extra_cycles,
        min_ways=min_ways,
        speedup_threshold=speedup_threshold,
        min_bandwidth_allocation=min_bandwidth_allocation,
        atd_decay=atd_decay,
        bandwidth_delay_decay=bandwidth_delay_decay,
        iters=iters,
        shard=shard,
    )[0]
