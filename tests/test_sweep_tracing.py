"""The sweep's own tracing: named scopes in the compiled programs, host
spans in a profiler trace, and the boundary greedy's trip counter."""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CBPParams, dispatch
from repro.core.x64 import x64_context
from repro.sim import memsys_jax, run_sweep, sweep
from repro.sim.memsys import FIXED_POINT_ITERS
from repro.sim.workloads import WORKLOADS

MIXES = [WORKLOADS["w1"], WORKLOADS["w2"]]
#: One reconfiguration boundary (at 10 ms) is enough to run the greedy.
TOTAL_MS = 12.0
SPANS = ("cbp.sweep.prepare", "cbp.sweep.collect", "cbp.sweep.baseline")


def _op_names(hlo_text: str):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def test_stacked_program_carries_both_scopes():
    plant = sweep.BatchedCMPPlant(MIXES)
    _specs, staged = sweep._stage_managers(
        plant, ["baseline", "CBP", "auction"], TOTAL_MS, CBPParams())
    with x64_context():
        names = _op_names(staged.fn.lower(*staged.args).compile().as_text())
    assert any(dispatch.GREEDY_SCOPE in n for n in names)
    assert any(dispatch.INTERVAL_SCOPE in n for n in names)
    # the registry's boundary branch runs under the greedy's scope too
    assert any(dispatch.GREEDY_SCOPE in n and "branch" in n for n in names)


def test_baseline_program_carries_the_interval_scope():
    plant = sweep.BatchedCMPPlant(MIXES)
    m, n = plant.n_mixes, plant.n_clients
    with x64_context():
        f64 = functools.partial(jnp.asarray, dtype=jnp.float64)
        params = {k: f64(v)
                  for k, v in memsys_jax.app_params(plant.apps).items()}
        lowered = memsys_jax._evaluate_jit.lower(
            params, f64(np.full((m, n), 16.0)), f64(np.full((m, n), 4.0)),
            f64(np.zeros((m, n))), f64(256.0), f64(64.0), f64(0.0),
            cache_partitioned=False, bandwidth_partitioned=False,
            iters=FIXED_POINT_ITERS)
        names = _op_names(lowered.compile().as_text())
    assert any(dispatch.INTERVAL_SCOPE in n for n in names)


def test_sweep_spans_nest_in_the_callers_span(tmp_path):
    run_sweep(MIXES, managers=["baseline", "CBP"], total_ms=TOTAL_MS)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller.sweep"):
            run_sweep(MIXES, managers=["baseline", "CBP"], total_ms=TOTAL_MS)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [e for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes for line in plane.lines for e in line.events]
    caller = [e for e in events if e.name == "caller.sweep"]
    assert len(caller) == 1
    c = caller[0]
    got = sorted((e for e in events if e.name in SPANS),
                 key=lambda e: e.start_ns)
    assert [e.name for e in got] == list(SPANS)
    for e in got:
        assert c.start_ns <= e.start_ns and e.end_ns <= c.end_ns
    for a, b in zip(got, got[1:]):
        assert a.end_ns <= b.start_ns


def test_span_totals_and_reset():
    dispatch.reset_device_dispatches()
    for _ in range(2):
        run_sweep(MIXES, managers=["baseline", "CBP"], total_ms=TOTAL_MS)
    totals = dispatch.span_seconds()
    assert set(totals) == set(SPANS) and all(s > 0 for s in totals.values())
    assert dispatch.device_dispatches() == 4
    dispatch.reset_device_dispatches()
    assert dispatch.span_seconds() == {}
    assert dispatch.greedy_trips() == 0


def test_greedy_trips_count_only_dynamic_cache_managers():
    dispatch.reset_device_dispatches()
    run_sweep(MIXES, managers=["baseline", "CBP"], total_ms=TOTAL_MS)
    trips = dispatch.greedy_trips()
    assert trips > 0
    assert trips % 4 == 0        # four body applications per while trip
    # the same sweep counts the same trips: the counter only adds
    run_sweep(MIXES, managers=["baseline", "CBP"], total_ms=TOTAL_MS)
    assert dispatch.greedy_trips() == 2 * trips

    dispatch.reset_device_dispatches()
    run_sweep(MIXES, managers=["baseline", "equal on"], total_ms=TOTAL_MS)
    assert dispatch.greedy_trips() == 0
    assert dispatch.device_dispatches() == 2
