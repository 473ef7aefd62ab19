"""Device-dispatch counter, greedy-trip counter and host spans for the sweep
substrate.

Every host->device program invocation on the sweep hot path (the jitted
interval model, the batched Lookahead allocator, and the fused Fig. 8
timeline) records itself here.  Tests and the CI sweep smoke use the
counter to enforce the PR 3 contract: a full ``run_sweep`` over the
Table-3 managers is **one stacked timeline program** plus a single
baseline evaluation, with no per-segment or per-manager program.

This counts Python-level jitted-entry invocations (the unit the host loop
pays for), not XLA-internal executions; it is the batched analogue of
:func:`repro.core.cache_controller.allocator_calls`.  It does not count
the small eager programs outside those entries: the per-spec slices of
the stacked program's outputs (``timeline_jax.PendingTimelines``) and the
device->host fetch of each result field are launches and round trips of
their own, which only a profiler trace shows.

:func:`greedy_trips` counts the boundary greedy's body applications
inside the stacked timeline programs (four per ``while_loop`` trip), summed
over every boundary and every shard, as the programs report them when
their results are fetched.

:func:`span` opens a named host span: a ``jax.profiler.TraceAnnotation``,
so a profiler trace shows it on the same clock as the device's ops, and a
wall-time total per name (:func:`span_seconds`) that needs no profiler.

:func:`reset_device_dispatches` opens a new counting window for all of
them at once: the dispatch and greedy-trip counters and the span totals.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import jax

#: ``jax.named_scope`` names of the device work inside the sweep's
#: programs: the interval model's fixed point (the stacked scan's step and
#: the baseline program) and the boundary greedy (Lookahead and the
#: registry's boundary branches).
INTERVAL_SCOPE = "cbp.interval"
GREEDY_SCOPE = "cbp.greedy"

_DISPATCHES = 0
_GREEDY_TRIPS = 0
_SPAN_S: Dict[str, float] = {}


def device_dispatches() -> int:
    """Total counted device-program invocations in this process."""
    return _DISPATCHES


def reset_device_dispatches() -> None:
    """Zero every counter of this module: dispatches, greedy trips and the
    span totals."""
    global _DISPATCHES, _GREEDY_TRIPS
    _DISPATCHES = 0
    _GREEDY_TRIPS = 0
    _SPAN_S.clear()


def record_dispatch(n: int = 1) -> None:
    """Called by the jitted-entry wrappers; ``n`` programs launched."""
    global _DISPATCHES
    _DISPATCHES += n


def greedy_trips() -> int:
    """Boundary-greedy body applications reported since the last reset."""
    return _GREEDY_TRIPS


def record_greedy_trips(n: int) -> None:
    """Called when a stacked timeline result is fetched to the host."""
    global _GREEDY_TRIPS
    _GREEDY_TRIPS += int(n)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """A named host span: a profiler annotation plus a wall-time total."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    _SPAN_S[name] = _SPAN_S.get(name, 0.0) + time.perf_counter() - t0


def span_seconds() -> Dict[str, float]:
    """Wall seconds per span name since the last reset."""
    return dict(_SPAN_S)
