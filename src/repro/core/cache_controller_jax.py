"""Batched JAX port of the UCP Lookahead allocator (paper §3.2.1).

:func:`lookahead_allocate` takes ``(..., n, total_units + 1)`` utility
curves and returns ``(..., n)`` integer allocations — the whole batch runs
as ONE jitted device call, a bounded-trip ``lax.while_loop`` greedy over a
masked marginal-utility argmax.  This is what lets the sweep substrate
(:mod:`repro.sim.sweep`) reconfigure every mix of a Table-3 sweep without a
single per-mix host allocator call.

Parity contract: bit-identical to the numpy golden reference
(:func:`repro.core.cache_controller.lookahead_allocate`) away from tie
knife-edges, under the shared deterministic tie-breaks (lowest client index
wins equal marginal utility; smallest step wins within a client; the
zero-utility spread orders by remaining gain with a stable sort).  Enforced
by ``tests/test_cache_controller_jax.py``.  Change the numpy reference
first, then mirror here.

:func:`lookahead_allocate_masked` is the CPpf variant
(:func:`repro.core.cache_controller.cppf_allocate`): inactive clients are
pinned at the floor and the greedy runs over the active subset, matching
the scalar subset call exactly (including the subset-local spread column).

``min_units`` may vary per batch element (a traced array), which is how
``run_sweep(param_grid=...)`` batches over ``CBPParams.min_ways``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import record_dispatch
from repro.core.x64 import x64_context


def _resolve_backend(backend):
    """``None`` -> the batched while_loop on every platform: it is the path
    the parity tests pin.  The Pallas kernel runs only when asked for by
    name (it does not lower for a TPU yet; off-TPU it runs interpreted)."""
    if backend is None:
        backend = "jax"
    if backend not in ("jax", "pallas"):
        raise ValueError(f"unknown lookahead backend {backend!r}")
    return backend


def _shift_clamped(x, offset):
    """``x[..., min(offset + c, W - 1)]`` for every column ``c < W``.

    A per-row left shift with the edge column repeated past the end, built
    from static shifts by ``2**b`` and selects on bit ``b`` of ``offset``
    (``offset`` broadcasts against ``x.shape[:-1]`` and lies in
    ``[0, W - 1]``).  Clamped shifts compose (``min(min(a, e) + s, e) ==
    min(a + s, e)``), so the result equals the clamped gather it replaces,
    bit for bit, without a gather.
    """
    W = x.shape[-1]
    for b in range((W - 1).bit_length()):
        s = 1 << b
        edge = jnp.broadcast_to(x[..., -1:], x.shape[:-1] + (min(s, W),))
        moved = jnp.concatenate([x[..., s:], edge], axis=-1)
        x = jnp.where(((offset >> b) & 1).astype(bool)[..., None], moved, x)
    return x


def _pick_client(x, pick):
    """``x[b, j_b]`` where ``pick[b]`` is one-hot at ``j_b`` over the client
    axis 1 of ``x`` — a masked reduction instead of a gather.  Floats take
    ``max`` over a ``-inf`` fill and integers ``sum`` over a zero fill, both
    of which return the picked element bit for bit."""
    mask = pick.reshape(pick.shape + (1,) * (x.ndim - 2))
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.where(mask, x, -jnp.inf).max(axis=1)
    return jnp.where(mask, x, 0).sum(axis=1, dtype=x.dtype)


@functools.partial(jax.jit, static_argnames=("total_units",))
def _greedy_loop(
    curves: jnp.ndarray,     # (B, n, U + 1) float64
    min_units: jnp.ndarray,  # (B,) int
    active: jnp.ndarray,     # (B, n) bool
    remaining: jnp.ndarray,  # (B,) int — top curve column per batch element
    total_units: int,
):
    """Bounded-trip while_loop greedy over cached per-client best steps.

    The reference recomputes every client's best ``(mu, k)`` each greedy
    iteration, but between iterations only the stepped client's curve
    position changes; any other cached best stays the exact
    argmax-with-tie-breaks as long as its ``k`` still fits the shrunken
    balance cap (the argmax over a subset that still contains the old
    argmax is unchanged).  So: one full ``(B, n, U)`` pass prefills the
    cache, then each trip refreshes at most ONE stale client per batch
    element — one ``(B, U)`` mu scan instead of ``n`` — and rows with a
    fully valid cache take their greedy step in the same trip.

    The prefill and the body hold no gather.  XLA:TPU reads a gather
    element by element (an f64 one twice, once per 32-bit half), so a
    gathered body costs time in proportion to its rows: on a v5e 5.5 us
    per row per body application, 97% of the greedy.  Instead the
    stepped client's curve, allocation and cap are picked by a one-hot
    masked reduction over the client axis (:func:`_pick_client`), and
    ``curve[min(have + k, U)]`` is a log-step barrel shift along the
    curve axis (:func:`_shift_clamped`): ``ceil(log2(U + 1))`` static
    shifts and selects, the same values bit for bit.  The pick reads the
    whole ``(B, n, U + 1)`` block where a gather reads one row, which
    costs wall time on a CPU; it is one path on every platform all the
    same.

    A batch element whose best mu goes non-positive is *stuck*: its
    allocation no longer changes, so its mus can't either — the loop
    retires it and the reference's zero-utility spread (distribute the
    whole balance by remaining potential gain) is applied ONCE, after the
    loop, to every retired element.

    Returns ``(alloc, balance, stuck, it)`` — the greedy allocation, the
    undistributed balance for :func:`_zero_spread`, the per-row stuck
    flags, and the body-application count (four per while trip), which the
    trip-bound regression test audits.
    """
    B, n, _ = curves.shape
    U = total_units
    ks = jnp.arange(1, U + 1, dtype=jnp.int32)                 # (U,)
    ksf = ks.astype(curves.dtype)
    neg_inf = jnp.array(-jnp.inf, curves.dtype)
    iota_n = jnp.arange(n, dtype=jnp.int32)

    min32 = min_units.astype(jnp.int32)
    alloc0 = jnp.broadcast_to(min32[:, None], (B, n))
    balance0 = U - n * min32
    rem32 = remaining.astype(jnp.int32)
    stuck0 = jnp.zeros((B,), dtype=bool)

    def caps(alloc, balance):
        """Per-client step cap: k <= balance, alloc + k inside the
        (sub)curve, inactive clients excluded."""
        cap = jnp.minimum(balance[:, None], rem32[:, None] - alloc)
        return jnp.where(active, cap, 0)                        # (B, n)

    # ---- prefill: every client's best (mu, k), one full pass --------- #
    cap0 = caps(alloc0, balance0)
    # Column c of the shifted curve is curves[..., min(alloc0 + c, U)]:
    # column 0 is the base, columns 1..U the clamped step targets.
    shifted0 = _shift_clamped(curves, alloc0)
    gain = shifted0[..., 1:] - shifted0[..., :1]
    mus = jnp.where(ks[None, None, :] <= cap0[:, :, None],
                    gain / ksf, neg_inf)
    # argmax picks the FIRST max -> smallest k: the reference tie-break.
    k_c0 = jnp.where(cap0 > 0,
                     jnp.argmax(mus, axis=-1).astype(jnp.int32) + 1, 0)
    mu_c0 = jnp.where(cap0 > 0, jnp.max(mus, axis=-1), neg_inf)
    dirty0 = jnp.zeros((B, n), dtype=bool)

    def cond(state):
        _alloc, balance, stuck, _mu, _k, _dirty, it = state
        # Trip bound: <= U greedy steps per row, and between consecutive
        # steps each client refreshes at most once -> (n + 2) * U is safe.
        return (it < (n + 2) * U) & jnp.any((balance > 0) & ~stuck)

    def body(state):
        alloc, balance, stuck, mu_c, k_c, dirty, it = state
        cap_now = caps(alloc, balance)
        # ---- refresh one stale cache entry per row ------------------- #
        invalid = active & (dirty | (k_c > cap_now))
        n_inv = jnp.sum(invalid, axis=-1)                       # (B,)
        j = jnp.argmax(invalid, axis=-1).astype(jnp.int32)      # first stale
        has_inv = n_inv > 0
        pick_j = iota_n[None, :] == j[:, None]                  # (B, n)
        c_j = _pick_client(curves, pick_j)                      # (B, U + 1)
        have_j = _pick_client(alloc, pick_j)
        cap_j = _pick_client(cap_now, pick_j)
        shifted_j = _shift_clamped(c_j, have_j)
        gain_j = shifted_j[:, 1:] - shifted_j[:, :1]
        mu_vec = jnp.where(ks[None, :] <= cap_j[:, None],
                           gain_j / ksf, neg_inf)
        k_j = jnp.where(cap_j > 0,
                        jnp.argmax(mu_vec, axis=-1).astype(jnp.int32) + 1, 0)
        mu_j = jnp.where(cap_j > 0, jnp.max(mu_vec, axis=-1), neg_inf)
        at_j = (iota_n[None, :] == j[:, None]) & has_inv[:, None]
        mu_c = jnp.where(at_j, mu_j[:, None], mu_c)
        k_c = jnp.where(at_j, k_j[:, None], k_c)
        dirty = dirty & ~at_j

        # ---- greedy step for rows whose cache is now fully valid ----- #
        # argmax over clients picks the FIRST max -> lowest client index.
        i_best = jnp.argmax(mu_c, axis=-1).astype(jnp.int32)    # (B,)
        mu_sel = jnp.max(mu_c, axis=-1)
        k_sel = _pick_client(k_c, iota_n[None, :] == i_best[:, None])
        live = (balance > 0) & ~stuck
        ready = live & (n_inv <= 1)
        do_greedy = ready & (mu_sel > 0.0)
        at_i = (iota_n[None, :] == i_best[:, None]) & do_greedy[:, None]
        alloc = alloc + jnp.where(at_i, k_sel[:, None], 0)
        balance = balance - jnp.where(do_greedy, k_sel, 0)
        dirty = dirty | at_i
        stuck = stuck | (ready & ~(mu_sel > 0.0))
        return alloc, balance, stuck, mu_c, k_c, dirty, it + 1

    def body_quad(state):
        # Four body applications per while trip: once a row is finished
        # (balance exhausted or stuck) body is a no-op for it, so the
        # unroll preserves the exact greedy trajectory while quartering
        # the loop's per-trip overhead on CPU (the trips are tiny-op
        # bound — cond + carry rotation cost as much as the body).
        return body(body(body(body(state))))

    alloc, balance, stuck, it = (lambda s: (s[0], s[1], s[2], s[6]))(
        jax.lax.while_loop(
            cond, body_quad,
            (alloc0, balance0, stuck0, mu_c0, k_c0, dirty0, jnp.int32(0))))
    return alloc, balance, stuck, it


def _zero_spread(curves, alloc, balance, active, remaining):
    """The reference's even-spread branch: distribute the undistributed
    balance by remaining potential gain (stable order).  Runs once, outside
    the greedy loop, for elements retired with balance left — shared by the
    while_loop and Pallas backends."""
    B, n, _ = curves.shape
    cur = jnp.take_along_axis(curves, alloc[:, :, None], -1)[:, :, 0]
    top = jnp.take_along_axis(
        curves, jnp.broadcast_to(remaining[:, None, None], (B, n, 1)),
        -1)[:, :, 0]
    key = jnp.where(active, -(top - cur), jnp.inf)
    order = jnp.argsort(key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)          # inverse permutation
    n_act = jnp.maximum(jnp.sum(active, axis=-1), 1)            # (B,)
    share = (balance[:, None] // n_act[:, None]
             + (rank < (balance % n_act)[:, None]))
    need = balance > 0
    alloc = jnp.where((need[:, None]) & active, alloc + share, alloc)
    return alloc


def _greedy_core(curves, min_units, active, remaining, total_units: int,
                 backend=None, with_trips: bool = False):
    """Backend-dispatched greedy + shared spread.

    ``backend="jax"`` runs the batched incremental-refresh while_loop;
    ``backend="pallas"`` runs the per-row VMEM-resident kernel
    (:mod:`repro.kernels.lookahead_greedy`).  Both feed the same
    :func:`_zero_spread`, so they are interchangeable bit for bit.
    ``with_trips=True`` returns ``(alloc, trips)``: the while_loop's
    body-application count (int32), or ``None`` from the kernel, which
    has no such loop.
    """
    backend = _resolve_backend(backend)
    trips = None
    if backend == "pallas":
        from repro.kernels.lookahead_greedy import ops as _lookahead_ops
        alloc, balance = _lookahead_ops.lookahead_greedy(
            curves, min_units, active.astype(jnp.int32),
            remaining, total_units=total_units)
    else:
        alloc, balance, _stuck, trips = _greedy_loop(
            curves, min_units, active, remaining,
            total_units=total_units)
    out = _zero_spread(curves, alloc, balance, active, remaining)
    return (out, trips) if with_trips else out


def lookahead_traced(curves, min_units, total_units: int, backend=None):
    """Traced Lookahead over ``(B, n, U+1)`` curves -> ``(B, n)`` int32.

    For use *inside* jitted programs (the fused Fig. 8 timeline scans over
    this at every reconfiguration boundary).  ``curves`` must already be
    float64 and ``min_units`` an integer ``(B,)`` array; the host-side
    feasibility checks are the caller's responsibility (hoisted out of the
    traced region, see :mod:`repro.sim.timeline_jax`).
    """
    B, n, _ = curves.shape
    return _greedy_core(
        curves, min_units, jnp.ones((B, n), dtype=bool),
        jnp.full((B,), total_units, dtype=jnp.int32),
        total_units=total_units, backend=backend)


def lookahead_masked_traced(curves, min_units, active, total_units: int,
                            backend=None):
    """Traced CPpf allocation (:func:`lookahead_allocate_masked` inside jit).

    Pins inactive clients at the floor and runs the greedy over the active
    subset; the all-inactive fallback (even split, remainder to the lowest
    indices) is folded in as a ``where`` so the whole decision stays on
    device.  Returns ``(alloc, trips)``: the greedy's body applications as
    :func:`_greedy_core` counts them.
    """
    B, n, _ = curves.shape
    min32 = min_units.astype(jnp.int32)
    remaining = (total_units
                 - min32 * (n - active.sum(axis=-1).astype(jnp.int32)))
    out, trips = _greedy_core(curves, min_units, active, remaining,
                              total_units=total_units, backend=backend,
                              with_trips=True)
    none_active = ~active.any(axis=-1)
    extra = total_units - n * min32
    even = (min32[:, None] + extra[:, None] // n
            + (jnp.arange(n, dtype=jnp.int32)[None, :]
               < (extra % n)[:, None]))
    return jnp.where(none_active[:, None], even, out), trips


def _validate(curves: np.ndarray, total_units: int,
              min_units: np.ndarray) -> None:
    if curves.shape[-1] != total_units + 1:
        raise ValueError(
            f"utility curves must have {total_units + 1} points, "
            f"got {curves.shape[-1]}")
    n = curves.shape[-2]
    if np.any(min_units * n > total_units):
        raise ValueError("min_units * n exceeds capacity")


def _flatten(curves: np.ndarray, min_units) -> tuple:
    batch_shape = curves.shape[:-2]
    flat = curves.reshape((-1,) + curves.shape[-2:])
    mus = np.broadcast_to(
        np.asarray(min_units, dtype=np.int64), batch_shape).reshape(-1)
    if flat.shape[0] == 0:
        raise ValueError("empty batch")
    return batch_shape, flat, mus


def lookahead_allocate(
    utility_curves,
    total_units: int,
    min_units=4,
    backend=None,
) -> np.ndarray:
    """Batched Lookahead: ``(..., n, U+1)`` curves -> ``(..., n)`` ints.

    Drop-in batched counterpart of the numpy reference; ``min_units`` may
    be a scalar or broadcast against the leading batch axes.
    """
    curves = np.asarray(utility_curves, dtype=np.float64)
    if curves.ndim < 2:
        raise ValueError("utility curves must be at least 2-D")
    batch_shape, flat, mus = _flatten(curves, min_units)
    _validate(curves, total_units, mus)
    B, n, _ = flat.shape
    record_dispatch()
    with x64_context():
        out = _greedy_core(
            jnp.asarray(flat, dtype=jnp.float64),
            jnp.asarray(mus),
            jnp.ones((B, n), dtype=bool),
            jnp.full((B,), total_units, dtype=jnp.int64),
            total_units=int(total_units), backend=backend)
        out = np.asarray(out)
    assert (out.sum(axis=-1) == total_units).all()
    return out.reshape(batch_shape + (n,)).astype(np.int64)


@functools.lru_cache(maxsize=None)
def _compiled_grouped(total_units_key: tuple, backend: str):
    """One jitted program running a greedy per capacity group.

    Groups with different ``total_units`` cannot share one ``_greedy_loop``
    call (the capacity is a static argument and fixes the curve width), but
    they CAN share one program: the per-group greedies are independent
    subgraphs of a single jit, so a multi-capacity plan costs one dispatch
    — the same bucketing trick as ``timeline_jax._compiled_buckets``.
    """

    def run(groups):
        outs = []
        for (curves, mins), units in zip(groups, total_units_key):
            B, n, _ = curves.shape
            outs.append(_greedy_core(
                curves, mins, jnp.ones((B, n), dtype=bool),
                jnp.full((B,), units, dtype=jnp.int64),
                total_units=units, backend=backend))
        return tuple(outs)

    return jax.jit(run)


def lookahead_allocate_grouped(
    curve_groups,
    total_units_list,
    min_units=4,
    backend=None,
):
    """Batched Lookahead over groups with *different* capacities — one call.

    Args:
      curve_groups: sequence of ``(B_g, n_g, U_g + 1)`` float64 curve
        batches, one per capacity group.
      total_units_list: per-group capacity (``U_g``), same length.
      min_units: scalar floor, or a sequence of per-group scalars /
        ``(B_g,)`` arrays.
      backend: as in :func:`lookahead_allocate`.

    Returns:
      List of ``(B_g, n_g)`` int64 allocations, bit-identical per row to
      the scalar numpy reference.  The whole multi-group plan is ONE device
      dispatch (counter-gated by the runtime smoke) — this is what lets
      ``plan_matmul_blocks_batched`` plan shapes with different VMEM
      budgets in a single program.
    """
    if len(curve_groups) != len(total_units_list):
        raise ValueError("one total_units per curve group required")
    if len(curve_groups) == 0:
        raise ValueError("empty group list")
    if np.isscalar(min_units):
        min_units = [min_units] * len(curve_groups)
    prepared = []
    for curves, units, mus in zip(curve_groups, total_units_list, min_units):
        curves = np.asarray(curves, dtype=np.float64)
        if curves.ndim != 3:
            raise ValueError("grouped curves must be (B, n, U + 1)")
        B, n, _ = curves.shape
        mus = np.broadcast_to(np.asarray(mus, dtype=np.int64), (B,))
        _validate(curves, int(units), mus)
        prepared.append((curves, int(units), mus))
    backend = _resolve_backend(backend)
    fn = _compiled_grouped(tuple(u for _, u, _m in prepared), backend)
    record_dispatch()
    with x64_context():
        outs = fn(tuple((jnp.asarray(c, dtype=jnp.float64), jnp.asarray(m))
                        for c, _u, m in prepared))
        outs = [np.asarray(o) for o in outs]
    for out, (_c, units, _m) in zip(outs, prepared):
        assert (out.sum(axis=-1) == units).all()
    return [o.astype(np.int64) for o in outs]


def lookahead_allocate_masked(
    utility_curves,
    total_units: int,
    min_units,
    active,
    backend=None,
) -> np.ndarray:
    """Batched CPpf allocation: pin inactive clients at the floor, UCP over
    the active subset (bit-parity with
    :func:`repro.core.cache_controller.cppf_allocate` per batch element).
    """
    curves = np.asarray(utility_curves, dtype=np.float64)
    if curves.ndim < 2:
        raise ValueError("utility curves must be at least 2-D")
    batch_shape, flat, mus = _flatten(curves, min_units)
    _validate(curves, total_units, mus)
    B, n, _ = flat.shape
    act = np.broadcast_to(
        np.asarray(active, dtype=bool), batch_shape + (n,)).reshape(B, n)
    # The scalar path runs the greedy on curves sliced to the capacity left
    # after pinning — column `remaining` is that slice's last column, which
    # the spread key reads.
    remaining = total_units - mus * (n - act.sum(axis=-1))
    record_dispatch()
    with x64_context():
        out = _greedy_core(
            jnp.asarray(flat, dtype=jnp.float64),
            jnp.asarray(mus),
            jnp.asarray(act),
            jnp.asarray(remaining),
            total_units=int(total_units), backend=backend)
        out = np.asarray(out)
    none_active = ~act.any(axis=-1)
    if none_active.any():
        # All clients pinned: split evenly, remainder to the lowest indices
        # (the reference's fixed all-friendly branch).
        extra = total_units - n * mus
        even = (mus + extra // n)[:, None] + (
            np.arange(n)[None, :] < (extra % n)[:, None])
        out = np.where(none_active[:, None], even, out)
    assert (out.sum(axis=-1) == total_units).all()
    return out.reshape(batch_shape + (n,)).astype(np.int64)
