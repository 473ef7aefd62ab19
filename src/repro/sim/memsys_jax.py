"""Batched JAX port of the CMP interval model (:mod:`repro.sim.memsys`).

Same math, same constants, same fixed-point iteration as the numpy
reference — but written in pure ``jax.numpy`` so one jitted device call can
evaluate arbitrarily many (workload mix, allocation) pairs at once.  All
array arguments broadcast against shape ``(..., n)``; adding a leading mix
or candidate-allocation axis batches the whole solve, which is what the
Table-3 sweep runner (:mod:`repro.sim.sweep`) builds on.

Contract: for identical inputs, :func:`evaluate` / :func:`utility_curves`
here must match ``memsys.evaluate`` / ``memsys.utility_curves`` to within
1e-5 relative tolerance (enforced by ``tests/test_sim_sweep.py``).  The
solve runs in float64 (:func:`repro.core.x64.x64_context`) so the parity
gap is dominated by op-ordering, not precision.  The numpy implementation stays
the golden reference — change that first, then mirror here.
"""
from __future__ import annotations

import functools
from typing import Dict, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import INTERVAL_SCOPE, record_dispatch
from repro.core.x64 import x64_context
from repro.sim.apps import MODEL_FIELDS, AppArrays
from repro.sim.memsys import (
    BANK_SKEW,
    DAMPING,
    DRAM_LAT_NS,
    FIXED_POINT_ITERS,
    FREQ_GHZ,
    IF_SKEW,
    LINE_BYTES,
    PF_QUEUE_WEIGHT,
    Q_SCALE_NS,
    RHO_MAX,
    SteadyState,
)

#: AppArrays fields the model consumes (single source: apps.MODEL_FIELDS).
PARAM_FIELDS = MODEL_FIELDS

Params = Dict[str, jnp.ndarray]


def app_params(apps: Union[AppArrays, Params]) -> Params:
    """Numeric model parameters as a dict-of-arrays pytree, shape (..., n)."""
    if isinstance(apps, AppArrays):
        return {f: np.asarray(getattr(apps, f), dtype=np.float64)
                for f in PARAM_FIELDS}
    return {f: apps[f] for f in PARAM_FIELDS}


def mpki_curve(params: Params, units: jnp.ndarray) -> jnp.ndarray:
    """JAX mirror of :func:`repro.sim.memsys.mpki_curve`."""
    u = jnp.maximum(units, 1.0)
    span = params["mpki_min_alloc"] - params["mpki_floor"]
    return params["mpki_floor"] + span * jnp.exp(-(u - 4.0) / params["ws_units"])


def _bank_affinity(n_apps: int, n_banks: int, dtype) -> jnp.ndarray:
    """JAX mirror of :func:`repro.sim.memsys.bank_affinity` (static banks)."""
    i = jnp.arange(n_apps, dtype=dtype)[:, None]
    b = jnp.arange(n_banks, dtype=dtype)[None, :]
    a = BANK_SKEW ** jnp.mod(i + b, float(n_banks))
    return a / a.sum(axis=-1, keepdims=True)


def _banked_queueing(traffic_q, bw, banks, max_banks: int):
    """Affinity-weighted per-bank queueing with a *traced* bank count.

    ``banks`` broadcasts against ``(..., n)`` (float, >= 1); ``max_banks``
    is the static bank-axis width.  Rows with ``banks == 1`` reduce
    BIT-identically to the flat partitioned channel model: affinity is
    exactly 1.0 (skew**0 / 1.0), ``x * 1.0`` and ``x / 1.0`` are IEEE
    identities, masked banks contribute exact zeros to the queue sum and
    ``+inf`` to the cap min.  Returns ``(q_ns, cap_gbps)``.
    """
    n = traffic_q.shape[-1]
    i = jnp.arange(n, dtype=traffic_q.dtype)[:, None]           # (n, 1)
    b = jnp.arange(max_banks, dtype=traffic_q.dtype)[None, :]   # (1, MAXB)
    nb = jnp.broadcast_to(banks, traffic_q.shape)[..., None]    # (..., n, 1)
    active = b < nb
    a_raw = jnp.where(active, BANK_SKEW ** jnp.mod(i + b, nb), 0.0)
    aff = a_raw / a_raw.sum(axis=-1, keepdims=True)
    bank_bw = bw[..., None] / nb
    rho_b = traffic_q[..., None] * aff / jnp.maximum(bank_bw, 1e-6)
    rho_cb = jnp.clip(rho_b, 0.0, RHO_MAX)
    q_bank = Q_SCALE_NS * rho_cb / (1.0 - rho_cb)
    q_ns = jnp.sum(aff * q_bank, axis=-1)
    cap = jnp.min(
        jnp.where(active, bank_bw / jnp.where(active, aff, 1.0), jnp.inf),
        axis=-1)
    return q_ns, cap


@functools.partial(
    jax.jit,
    static_argnames=("cache_partitioned", "bandwidth_partitioned", "iters",
                     "bandwidth_banks"))
@jax.named_scope(INTERVAL_SCOPE)
def _evaluate_jit(
    params: Params,
    cache_units: jnp.ndarray,
    bw: jnp.ndarray,
    pf: jnp.ndarray,
    total_cache_units: jnp.ndarray,
    total_bandwidth_gbps: jnp.ndarray,
    llc_extra_cycles: jnp.ndarray,
    cache_partitioned: bool,
    bandwidth_partitioned: bool,
    iters: int,
    bandwidth_banks: int = 1,
):
    shape = jnp.broadcast_shapes(
        cache_units.shape, bw.shape, pf.shape, params["cpi_base"].shape)
    n = shape[-1]
    ipc0 = jnp.broadcast_to(1.0 / params["cpi_base"], shape)
    zeros = jnp.zeros(shape, ipc0.dtype)

    def body(_, carry):
        ipc, _q, _tr, mpki_eff, _ex, _oc = carry
        # ---- cache occupancy -------------------------------------------- #
        if cache_partitioned:
            occ = jnp.broadcast_to(cache_units, shape).astype(ipc.dtype)
        else:
            miss_rate = jnp.maximum(mpki_eff, 1e-3) * ipc
            share = miss_rate / jnp.sum(miss_rate, axis=-1, keepdims=True)
            occ = share * total_cache_units
        occ_eff = jnp.maximum(occ - params["pf_pollution"] * pf, 1.0)

        # ---- prefetch-adjusted miss stream ------------------------------ #
        m = mpki_curve(params, occ_eff)
        covered = params["pf_cov"] * pf * m
        exposed = m - covered * params["pf_hide"]
        useless = covered * (1.0 / jnp.maximum(params["pf_acc"], 1e-3) - 1.0)
        reqki = m * (1.0 + params["wb_frac"]) + useless
        reqki_q = ((m - covered) + m * params["wb_frac"]
                   + PF_QUEUE_WEIGHT * (covered + useless))

        # ---- memory queuing --------------------------------------------- #
        traffic = ipc * FREQ_GHZ * reqki * LINE_BYTES / 1000.0
        traffic_q = ipc * FREQ_GHZ * reqki_q * LINE_BYTES / 1000.0
        if bandwidth_partitioned and bandwidth_banks > 1:
            # Banked tokens (mirror of the numpy golden): affinity-weighted
            # per-bank M/M/1 queues, cap set by the first saturated bank.
            aff = _bank_affinity(n, bandwidth_banks, ipc.dtype)
            bank_bw = bw[..., None] / float(bandwidth_banks)
            rho_b = traffic_q[..., None] * aff / jnp.maximum(bank_bw, 1e-6)
            rho_cb = jnp.clip(rho_b, 0.0, RHO_MAX)
            q_bank = Q_SCALE_NS * rho_cb / (1.0 - rho_cb)
            q_ns = jnp.sum(aff * q_bank, axis=-1)
            cap_gbps = jnp.broadcast_to(
                jnp.min(bank_bw / aff, axis=-1), shape).astype(ipc.dtype)
        elif bandwidth_partitioned:
            rho = traffic_q / jnp.maximum(bw, 1e-6)
            cap_gbps = jnp.broadcast_to(bw, shape).astype(ipc.dtype)
            rho_c = jnp.clip(rho, 0.0, RHO_MAX)
            q_ns = Q_SCALE_NS * rho_c / (1.0 - rho_c)
        else:
            tot = jnp.sum(traffic_q, axis=-1, keepdims=True)
            rho = jnp.broadcast_to(tot / total_bandwidth_gbps, shape)
            tot_full = jnp.sum(traffic, axis=-1, keepdims=True)
            safe_tot = jnp.where(tot_full > 0, tot_full, 1.0)
            frac = jnp.where(tot_full > 0, traffic / safe_tot, 1.0 / n)
            cap_gbps = frac * total_bandwidth_gbps
            rho_c = jnp.clip(rho, 0.0, RHO_MAX)
            q_ns = Q_SCALE_NS * rho_c / (1.0 - rho_c)
            q_ns = q_ns * (1.0 + IF_SKEW * (1.0 - frac))

        # ---- IPC --------------------------------------------------------- #
        penalty_cyc = (DRAM_LAT_NS + q_ns) * FREQ_GHZ / params["mlp"]
        cpi = (params["cpi_base"]
               + params["apki"] / 1000.0 * llc_extra_cycles
               + exposed / 1000.0 * penalty_cyc)
        ipc_demand = 1.0 / cpi
        ipc_cap = RHO_MAX * cap_gbps / jnp.maximum(
            FREQ_GHZ * reqki * LINE_BYTES / 1000.0, 1e-9)
        ipc_new = jnp.minimum(ipc_demand, ipc_cap)
        ipc = DAMPING * ipc + (1.0 - DAMPING) * ipc_new
        return (ipc, q_ns, traffic, m, exposed, occ)

    init = (ipc0, zeros, zeros, zeros, zeros, zeros)
    return jax.lax.fori_loop(0, iters, body, init)


@jax.named_scope(INTERVAL_SCOPE)
def _evaluate_rowflags(
    params: Params,
    cache_units: jnp.ndarray,
    bw: jnp.ndarray,
    pf: jnp.ndarray,
    total_cache_units,
    total_bandwidth_gbps,
    llc_extra_cycles,
    cache_partitioned: jnp.ndarray,
    bandwidth_partitioned: jnp.ndarray,
    iters: int,
    bandwidth_banks=None,
    max_banks: int = 1,
):
    """:func:`_evaluate_jit` with *traced per-row* partitioning flags.

    The stacked Fig. 8 timeline (:mod:`repro.sim.timeline_jax`) batches
    managers with different Table-3 modes into one program, so
    ``cache_partitioned`` / ``bandwidth_partitioned`` become boolean
    arrays broadcasting against the batch axes instead of static trace
    flags.  Both branches of each regime are computed and selected
    elementwise; every op of the selected branch is identical to the
    static-flag path, so per-row results are bit-identical to
    :func:`_evaluate_jit` with that row's flags (pinned by
    ``tests/test_timeline_fused.py``).  Meant to be called inside an
    enclosing jitted program — it is not jitted itself.

    ``bandwidth_banks`` (traced, broadcasting against the batch axes) and
    the static ``max_banks`` select the banked-token regime per row: when
    ``max_banks > 1`` every partitioned row goes through the generalized
    bank formula, whose 1-bank rows are bit-identical to the flat model
    (:func:`_banked_queueing`) — so mixing banked and flat rows in one
    stack preserves the stacked-vs-fused parity contract.
    """
    shape = jnp.broadcast_shapes(
        cache_units.shape, bw.shape, pf.shape, params["cpi_base"].shape)
    n = shape[-1]
    ipc0 = jnp.broadcast_to(1.0 / params["cpi_base"], shape)
    zeros = jnp.zeros(shape, ipc0.dtype)
    cache_part = jnp.broadcast_to(cache_partitioned, shape)
    bw_part = jnp.broadcast_to(bandwidth_partitioned, shape)

    def body(_, carry):
        ipc, _q, _tr, mpki_eff, _ex, _oc = carry
        # ---- cache occupancy -------------------------------------------- #
        occ_p = jnp.broadcast_to(cache_units, shape).astype(ipc.dtype)
        miss_rate = jnp.maximum(mpki_eff, 1e-3) * ipc
        share = miss_rate / jnp.sum(miss_rate, axis=-1, keepdims=True)
        occ = jnp.where(cache_part, occ_p, share * total_cache_units)
        occ_eff = jnp.maximum(occ - params["pf_pollution"] * pf, 1.0)

        # ---- prefetch-adjusted miss stream ------------------------------ #
        m = mpki_curve(params, occ_eff)
        covered = params["pf_cov"] * pf * m
        exposed = m - covered * params["pf_hide"]
        useless = covered * (1.0 / jnp.maximum(params["pf_acc"], 1e-3) - 1.0)
        reqki = m * (1.0 + params["wb_frac"]) + useless
        reqki_q = ((m - covered) + m * params["wb_frac"]
                   + PF_QUEUE_WEIGHT * (covered + useless))

        # ---- memory queuing --------------------------------------------- #
        traffic = ipc * FREQ_GHZ * reqki * LINE_BYTES / 1000.0
        traffic_q = ipc * FREQ_GHZ * reqki_q * LINE_BYTES / 1000.0
        if max_banks > 1:
            q_p, cap_p = _banked_queueing(
                traffic_q, bw, bandwidth_banks, max_banks)
            cap_p = jnp.broadcast_to(cap_p, shape).astype(ipc.dtype)
        else:
            rho_p = traffic_q / jnp.maximum(bw, 1e-6)
            rho_cp = jnp.clip(rho_p, 0.0, RHO_MAX)
            q_p = Q_SCALE_NS * rho_cp / (1.0 - rho_cp)
            cap_p = jnp.broadcast_to(bw, shape).astype(ipc.dtype)
        tot = jnp.sum(traffic_q, axis=-1, keepdims=True)
        rho_u = jnp.broadcast_to(tot / total_bandwidth_gbps, shape)
        tot_full = jnp.sum(traffic, axis=-1, keepdims=True)
        safe_tot = jnp.where(tot_full > 0, tot_full, 1.0)
        frac = jnp.where(tot_full > 0, traffic / safe_tot, 1.0 / n)
        rho_cu = jnp.clip(rho_u, 0.0, RHO_MAX)
        q_u = Q_SCALE_NS * rho_cu / (1.0 - rho_cu)
        q_u = q_u * (1.0 + IF_SKEW * (1.0 - frac))
        cap_gbps = jnp.where(bw_part, cap_p, frac * total_bandwidth_gbps)
        q_ns = jnp.where(bw_part, q_p, q_u)

        # ---- IPC --------------------------------------------------------- #
        penalty_cyc = (DRAM_LAT_NS + q_ns) * FREQ_GHZ / params["mlp"]
        cpi = (params["cpi_base"]
               + params["apki"] / 1000.0 * llc_extra_cycles
               + exposed / 1000.0 * penalty_cyc)
        ipc_demand = 1.0 / cpi
        ipc_cap = RHO_MAX * cap_gbps / jnp.maximum(
            FREQ_GHZ * reqki * LINE_BYTES / 1000.0, 1e-9)
        ipc_new = jnp.minimum(ipc_demand, ipc_cap)
        ipc = DAMPING * ipc + (1.0 - DAMPING) * ipc_new
        return (ipc, q_ns, traffic, m, exposed, occ)

    init = (ipc0, zeros, zeros, zeros, zeros, zeros)
    return jax.lax.fori_loop(0, iters, body, init)


def evaluate(
    apps: Union[AppArrays, Params],
    cache_units,
    bandwidth_gbps,
    prefetch_on,
    *,
    cache_partitioned: bool = True,
    bandwidth_partitioned: bool = True,
    total_cache_units: float = 256.0,
    total_bandwidth_gbps: float = 64.0,
    llc_extra_cycles: float = 0.0,
    bandwidth_banks: int = 1,
    iters: int = FIXED_POINT_ITERS,
) -> SteadyState:
    """Batched JAX counterpart of :func:`repro.sim.memsys.evaluate`.

    Returns a :class:`SteadyState` of device arrays; call ``np.asarray`` on
    the fields to bring them to host.
    """
    params = app_params(apps)
    record_dispatch()
    with x64_context():
        f64 = functools.partial(jnp.asarray, dtype=jnp.float64)
        p = {k: f64(v) for k, v in params.items()}
        ipc, q_ns, traffic, mpki_eff, exposed, occ = _evaluate_jit(
            p, f64(cache_units), f64(bandwidth_gbps), f64(prefetch_on),
            f64(total_cache_units), f64(total_bandwidth_gbps),
            f64(llc_extra_cycles),
            cache_partitioned=cache_partitioned,
            bandwidth_partitioned=bandwidth_partitioned,
            iters=iters, bandwidth_banks=bandwidth_banks)
    return SteadyState(
        ipc=ipc, queuing_delay_ns=q_ns, traffic_gbps=traffic,
        mpki=mpki_eff, exposed_mpki=exposed, occupancy_units=occ)


@functools.partial(jax.jit, static_argnames=("total_units",))
def _utility_curves_jit(
    params: Params,
    pf: jnp.ndarray,
    ipc: jnp.ndarray,
    duration_ms: jnp.ndarray,
    total_units: int,
):
    u = jnp.arange(total_units + 1, dtype=pf.dtype)          # (U+1,)
    p = {k: v[..., :, None] for k, v in params.items()}      # (..., n, 1)
    units = u - p["pf_pollution"] * pf[..., :, None]
    m = mpki_curve(p, units)                                 # (..., n, U+1)
    eff_miss = m * (1.0 - p["pf_cov"] * pf[..., :, None])
    hits = jnp.maximum(p["apki"] - eff_miss, 0.0)
    kilo_instr = ipc[..., :, None] * FREQ_GHZ * 1e6 * duration_ms / 1000.0
    return hits * kilo_instr


def utility_curves(
    apps: Union[AppArrays, Params],
    prefetch_on,
    ipc,
    total_units: int,
    duration_ms: float = 1.0,
) -> jnp.ndarray:
    """Batched JAX counterpart of :func:`repro.sim.memsys.utility_curves`.

    Shape ``(..., n, total_units + 1)`` — unlike the numpy reference this
    accepts leading batch axes on every argument.
    """
    params = app_params(apps)
    record_dispatch()
    with x64_context():
        f64 = functools.partial(jnp.asarray, dtype=jnp.float64)
        p = {k: f64(v) for k, v in params.items()}
        return _utility_curves_jit(
            p, f64(prefetch_on), f64(ipc), f64(duration_ms),
            total_units=int(total_units))
