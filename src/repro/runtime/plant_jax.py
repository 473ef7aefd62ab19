"""Fused TrainingPlant: the whole Fig. 8 knob schedule as ONE program.

:class:`repro.runtime.cbp_runtime.TrainingPlant` + the host
:class:`~repro.core.coordinator.CBPCoordinator` pay a host round-trip per
schedule segment — fine for a handful of intervals, a non-starter for the
per-step control loops the runtime wants.  This module ports the fused
fig8-timeline pattern (:mod:`repro.sim.timeline_jax`) to the training
plant: the segment list is encoded as a ``(kinds, durations, reconfigure)``
table and a single jitted ``lax.scan`` executes every segment — staging
buffer reallocation via ``lookahead_traced``, Algorithm-1 bandwidth splits
via ``allocate_bandwidth_jax``, Algorithm-2 A/B throttling via
``throttle_decision_jax`` at the interval boundaries — so a full knob
schedule is O(1) device dispatches per run (dispatch-counter gated by
``benchmarks/runtime_bench.py``).

The host coordinator path stays as the parity golden: with a step model
written once over an array namespace (see :mod:`repro.train.plant_model`)
the fused trajectory is BIT-identical to the host knob trajectory on 1 and
8 forced devices (``tests/test_plant_jax.py``), riding the same backend
ladder discipline as the simulator (numpy golden -> traced mirrors ->
fused scan).

The step model is the traced mirror of ``TrainingPlant.step_fn``::

    model(duration_ms, units_f64, bandwidth, prefetch_f64)
        -> (throughput (n,), queue_wait_ms (n,), utility_curves (n, U+1))

It must be pure ``jax.numpy`` (it runs inside the scan) and, for host
parity, arithmetically identical to the host step function — elementwise
float64 ops only, shared precomputed constants.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro.core.bandwidth_controller import check_bandwidth_floor
from repro.core.coordinator import IntervalRecord, fig8_schedule
from repro.core.dispatch import record_dispatch
from repro.core.prefetch_controller import throttle_decision_jax
from repro.core.types import CBPParams, Mode, PrefetchMode
from repro.core.x64 import x64_context

#: Segment kind codes — shared with the simulator's fused timeline so the
#: two fused subsystems cannot drift on schedule encoding.
from repro.sim.timeline_jax import NOOP, RUN, SAMPLE_OFF, SAMPLE_ON, segment_table


@dataclasses.dataclass
class PlantScheduleResult:
    """Per-segment knob trajectory + observations of one fused run.

    Rows are the *executed* (non-boundary) segments of the Fig. 8 schedule,
    in order — exactly the rows the host coordinator appends to
    ``history``.  ``kinds`` uses the ``timeline_jax`` codes
    (``SAMPLE_OFF/SAMPLE_ON/RUN``); host-derived trajectories reconstruct
    them from the same ``fig8_schedule`` call.
    """

    kinds: np.ndarray          # (S,) int32 segment kind codes
    t_ms: np.ndarray           # (S,) segment start times
    duration_ms: np.ndarray    # (S,)
    cache_units: np.ndarray    # (S, n) int64 — staging-buffer partitions
    bandwidth: np.ndarray      # (S, n) float64
    prefetch_on: np.ndarray    # (S, n) bool (as applied, incl. A/B forcing)
    ipc: np.ndarray            # (S, n) throughput observed per segment
    queuing_delay_ns: np.ndarray  # (S, n) queue wait observed per segment

    def mean_ipc(self) -> np.ndarray:
        """Time-weighted mean throughput per client (host ``mean_ipc``)."""
        w = self.duration_ms[:, None]
        return (self.ipc * w).sum(axis=0) / max(self.duration_ms.sum(), 1e-12)


def _segment_starts(durations: np.ndarray) -> np.ndarray:
    """Start times by the host coordinator's exact accumulation order."""
    t, starts = 0.0, []
    for d in durations:
        starts.append(t)
        t += float(d)
    return np.array(starts, dtype=np.float64)


def pin_f64(x, zero):
    """Pin a float64 value's bits: round-trip through int64, xor ``zero``.

    XLA's CPU backend emits mul+add chains with LLVM contraction (FMA — a
    single rounding where numpy rounds twice) and re-association enabled,
    and ``lax.optimization_barrier`` does NOT survive to that level — the
    mul and add still land in one fused loop body and contract.  Bit-exact
    parity with a numpy golden therefore needs each rounding point forced
    through the *integer* domain: LLVM cannot contract or re-associate
    across a bitcast, and the xor with a runtime-opaque zero (a traced
    input, so never constant-folded) keeps instcombine from collapsing the
    bitcast pair back to identity.  Value-wise this is the identity
    function.

    Traced step models that want bit-parity with their numpy twin should
    pin every binary-op result with this (see
    :func:`repro.train.plant_model.make_stream_plant_model`).
    """
    from jax import lax
    import jax.numpy as jnp

    return lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, jnp.int64) ^ zero, jnp.float64)


@functools.lru_cache(maxsize=None)
def _compiled_schedule(model: Callable, n: int, total_units: int,
                       cache_dynamic: bool, bandwidth_dynamic: bool,
                       prefetch_dynamic: bool, backend: Optional[str]):
    """Build + jit the scan for one (model, statics) combination.

    The scan step mirrors ``CBPCoordinator.run`` op for op: maybe
    reconfigure (cache -> ATD decay -> bandwidth, the paper's priority
    order), force the A/B prefetch setting, evaluate the plant model,
    accumulate the ATD counters and the decayed queuing-delay accumulator,
    and fold the throttle decision after each ``sample_on`` segment.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.cache_controller_jax import lookahead_traced

    def run(kinds, durs, reconf, units0, bw0, pf0, scalars, zero):
        min_ways, total_bw, min_bw, atd_decay, bw_decay, threshold = scalars

        def pin(x):
            return pin_f64(x, zero)

        def numpy_order_sum(vec):
            """Sum a static-length (n,) vector in numpy's exact rounding
            order.

            XLA lowers ``reduce`` through SIMD lanes whose accumulation
            tree differs from numpy's pairwise summation, so
            ``delay.sum()`` inside the scan lands 1 ulp off the host
            golden.  ``n`` is static, so the add tree unrolls in Python,
            mirroring numpy's ``pairwise_sum``: sequential under 8
            elements, 8-way unrolled accumulators up to 128, recursive
            halving (on an 8-multiple split) beyond.  Every partial sum is
            pinned so LLVM cannot re-associate the chain.
            """
            def psum(lo, m):
                if m < 8:
                    acc = vec[..., lo]
                    for i in range(lo + 1, lo + m):
                        acc = pin(acc + vec[..., i])
                    return acc
                if m <= 128:
                    r = [vec[..., lo + j] for j in range(8)]
                    i = 8
                    while i < m - (m % 8):
                        for j in range(8):
                            r[j] = pin(r[j] + vec[..., lo + i + j])
                        i += 8
                    res = pin(pin(pin(r[0] + r[1]) + pin(r[2] + r[3]))
                              + pin(pin(r[4] + r[5]) + pin(r[6] + r[7])))
                    for k in range(lo + i, lo + m):
                        res = pin(res + vec[..., k])
                    return res
                m2 = (m // 2) - ((m // 2) % 8)
                return pin(psum(lo, m2) + psum(lo + m2, m - m2))

            return psum(0, vec.shape[-1])[..., None]

        def allocate_bw(delay):
            """``allocate_bandwidth_jax`` with numpy's rounding pinned.

            Every float op result is pinned and the delay reduction runs
            in :func:`numpy_order_sum`'s order so Algorithm 1's splits
            match the host golden bit for bit inside the scan.
            """
            remaining = pin(total_bw - pin(min_bw * n))
            total_delay = numpy_order_sum(delay)
            share = pin(jnp.where(
                total_delay > 0,
                delay / jnp.where(total_delay > 0, total_delay, 1.0),
                1.0 / n))
            return pin(min_bw + pin(share * remaining))

        def reconfigure(args):
            units, bw, atd, bw_acc = args
            if cache_dynamic:
                units = lookahead_traced(
                    atd[None], min_ways[None], total_units,
                    backend=backend)[0].astype(units.dtype)
            atd = pin(atd * atd_decay)
            if bandwidth_dynamic:
                bw = allocate_bw(bw_acc)
            return units, bw, atd, bw_acc

        def step(carry, row):
            units, bw, pf, atd, bw_acc, off_ipc = carry
            kind, dt, rec = row
            units, bw, atd, bw_acc = jax.lax.cond(
                rec, reconfigure, lambda a: a, (units, bw, atd, bw_acc))
            is_off = kind == SAMPLE_OFF
            is_on = kind == SAMPLE_ON
            pf_used = jnp.where(is_off, False, jnp.where(is_on, True, pf))
            thr, wait, curves = model(
                dt, units.astype(jnp.float64), bw,
                pf_used.astype(jnp.float64))
            # Pin the model outputs too, in case the model skips its own
            # pinning — one canonical rounded tensor per observable.
            thr, wait, curves = pin(thr), pin(wait), pin(curves)
            # NOOP rows (stacking/trailing-boundary padding) are bitwise
            # no-ops: zero accumulation weight, no controller update.
            execs = kind != NOOP
            w = jnp.where(execs, dt, 0.0)
            atd = pin(atd + pin(curves * w))
            q_ns = pin(wait * 1e6)   # TrainingPlant.run_interval's scaling
            obs = pin(q_ns * w)
            decayed = pin(bw_decay * bw_acc)
            bw_acc = jnp.where(execs, pin(decayed + obs), bw_acc)
            off_ipc = jnp.where(is_off, thr, off_ipc)
            if prefetch_dynamic:
                pf = jnp.where(is_on,
                               throttle_decision_jax(thr, off_ipc, threshold),
                               pf)
            carry = (units, bw, pf, atd, bw_acc, off_ipc)
            return carry, (units, bw, pf_used, thr, q_ns)

        atd0 = jnp.zeros((n, total_units + 1), dtype=jnp.float64)
        acc0 = jnp.zeros((n,), dtype=jnp.float64)
        off0 = jnp.zeros((n,), dtype=jnp.float64)
        _carry, ys = jax.lax.scan(
            step, (units0, bw0, pf0, atd0, acc0, off0),
            (kinds, durs, reconf))
        return ys

    return jax.jit(run)


def run_fused_schedule(
    model: Callable,
    *,
    n_clients: int,
    total_units: int,
    total_bandwidth: float,
    total_ms: float,
    params: Optional[CBPParams] = None,
    cache_mode: Mode = Mode.DYNAMIC,
    bandwidth_mode: Mode = Mode.DYNAMIC,
    prefetch_mode: PrefetchMode = PrefetchMode.DYNAMIC,
    allocator_backend: Optional[str] = None,
) -> PlantScheduleResult:
    """Run a full Fig. 8 knob schedule as ONE jitted scan program.

    Feasibility checks (bandwidth floor, ``min_ways`` capacity, schedule
    well-formedness via ``CBPParams``) are hoisted out of the traced
    region, exactly like the simulator's fused path.
    """
    import jax.numpy as jnp

    params = params or CBPParams()
    n = n_clients
    check_bandwidth_floor(params.min_bandwidth_allocation, n, total_bandwidth)
    if params.min_ways * n > total_units:
        raise ValueError("min_ways * n_clients exceeds total_units")

    schedule = fig8_schedule(total_ms, params,
                             prefetch_mode == PrefetchMode.DYNAMIC)
    kinds, durs, reconf = segment_table(schedule)

    # Step 0 (Fig. 8): equal partitions, remainder to the lowest indices —
    # identical to CBPCoordinator._initial_allocation.
    units0 = np.full(n, total_units // n, dtype=np.int64)
    units0[: total_units - int(units0.sum())] += 1
    bw0 = np.full(n, total_bandwidth / n, dtype=np.float64)
    pf0 = np.full(n, prefetch_mode == PrefetchMode.ON, dtype=bool)

    fn = _compiled_schedule(
        model, n, int(total_units),
        cache_mode == Mode.DYNAMIC,
        bandwidth_mode == Mode.DYNAMIC,
        prefetch_mode == PrefetchMode.DYNAMIC,
        allocator_backend)
    record_dispatch()
    with x64_context():
        scalars = (jnp.asarray(params.min_ways, dtype=jnp.int64),
                   jnp.asarray(total_bandwidth, dtype=jnp.float64),
                   jnp.asarray(params.min_bandwidth_allocation,
                               dtype=jnp.float64),
                   jnp.asarray(params.atd_decay, dtype=jnp.float64),
                   jnp.asarray(params.bandwidth_delay_decay,
                               dtype=jnp.float64),
                   jnp.asarray(params.speedup_threshold, dtype=jnp.float64))
        units, bw, pf_used, thr, q_ns = fn(
            jnp.asarray(kinds), jnp.asarray(durs), jnp.asarray(reconf),
            jnp.asarray(units0), jnp.asarray(bw0), jnp.asarray(pf0),
            scalars, jnp.asarray(0, dtype=jnp.int64))
        units = np.asarray(units).astype(np.int64)
        bw = np.asarray(bw)
        pf_used = np.asarray(pf_used)
        thr = np.asarray(thr)
        q_ns = np.asarray(q_ns)

    live = kinds != NOOP
    return PlantScheduleResult(
        kinds=kinds[live],
        t_ms=_segment_starts(durs)[live],
        duration_ms=durs[live],
        cache_units=units[live],
        bandwidth=bw[live],
        prefetch_on=pf_used[live],
        ipc=thr[live],
        queuing_delay_ns=q_ns[live],
    )


class FusedTrainingPlant:
    """Device-resident sibling of ``TrainingPlant`` + ``CBPCoordinator``.

    Holds the traced step model and the capacity constants; each ``run``
    is one dispatch.  The host pair — ``CBPCoordinator(TrainingPlant(...,
    step_fn))`` with the numpy twin of the model — is the parity golden
    (see :func:`host_reference_run`).
    """

    def __init__(self, n_clients: int, total_buffer_units: int,
                 total_bandwidth_mbps: float, step_model: Callable,
                 allocator_backend: Optional[str] = None):
        self.n_clients = n_clients
        self.total_cache_units = total_buffer_units
        self.total_bandwidth = total_bandwidth_mbps
        self.allocator_backend = allocator_backend
        self._model = step_model

    def run(self, total_ms: float,
            params: Optional[CBPParams] = None,
            cache_mode: Mode = Mode.DYNAMIC,
            bandwidth_mode: Mode = Mode.DYNAMIC,
            prefetch_mode: PrefetchMode = PrefetchMode.DYNAMIC,
            ) -> PlantScheduleResult:
        return run_fused_schedule(
            self._model,
            n_clients=self.n_clients,
            total_units=self.total_cache_units,
            total_bandwidth=self.total_bandwidth,
            total_ms=total_ms,
            params=params,
            cache_mode=cache_mode,
            bandwidth_mode=bandwidth_mode,
            prefetch_mode=prefetch_mode,
            allocator_backend=self.allocator_backend)


def host_reference_run(
    step_fn: Callable,
    *,
    n_clients: int,
    total_units: int,
    total_bandwidth: float,
    total_ms: float,
    params: Optional[CBPParams] = None,
    cache_mode: Mode = Mode.DYNAMIC,
    bandwidth_mode: Mode = Mode.DYNAMIC,
    prefetch_mode: PrefetchMode = PrefetchMode.DYNAMIC,
) -> PlantScheduleResult:
    """The golden path: host ``CBPCoordinator`` over a host ``TrainingPlant``.

    Returns the knob trajectory in the same shape as
    :func:`run_fused_schedule` so parity tests and the runtime smoke can
    compare the two bit for bit.
    """
    from repro.core.coordinator import CBPCoordinator
    from repro.runtime.cbp_runtime import TrainingPlant

    params = params or CBPParams()
    plant = TrainingPlant(n_clients, total_units, total_bandwidth, step_fn)
    coord = CBPCoordinator(plant, params, cache_mode=cache_mode,
                           bandwidth_mode=bandwidth_mode,
                           prefetch_mode=prefetch_mode)
    history = coord.run(total_ms)
    schedule = fig8_schedule(total_ms, params,
                             prefetch_mode == PrefetchMode.DYNAMIC)
    kinds, _durs, _rec = segment_table(schedule)
    kinds = kinds[kinds != NOOP]
    return trajectory_from_history(history, kinds)


def trajectory_from_history(history: List[IntervalRecord],
                            kinds: Optional[Sequence[int]] = None,
                            ) -> PlantScheduleResult:
    """Convert a host coordinator ``history`` into a trajectory struct."""
    S = len(history)
    kinds = (np.asarray(kinds, dtype=np.int32) if kinds is not None
             else np.full(S, RUN, dtype=np.int32))
    return PlantScheduleResult(
        kinds=kinds,
        t_ms=np.array([r.t_ms for r in history], dtype=np.float64),
        duration_ms=np.array([r.duration_ms for r in history],
                             dtype=np.float64),
        cache_units=np.stack(
            [np.asarray(r.alloc.cache_units, dtype=np.int64)
             for r in history]),
        bandwidth=np.stack(
            [np.asarray(r.alloc.bandwidth, dtype=np.float64)
             for r in history]),
        prefetch_on=np.stack(
            [np.asarray(r.alloc.prefetch_on, dtype=bool) for r in history]),
        ipc=np.stack([np.asarray(r.stats.ipc, dtype=np.float64)
                      for r in history]),
        queuing_delay_ns=np.stack(
            [np.asarray(r.stats.queuing_delay_ns, dtype=np.float64)
             for r in history]),
    )
