"""Entry: back-to-back ``repro.sim.run_sweep`` calls over a fixed batch of
mixes, every manager of the cell, checked against the numpy reference.

The configuration file gives ``total_ms`` simulated per mix and
``managers`` (absent: every registered manager); the cell's file gives
the ``limits`` of the comparison.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
import types
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class State:
    names: List[str]
    mixes: List[List[str]]
    managers: List[str]
    total_ms: float


def _sweep(state: State):
    from repro.sim import run_sweep

    return run_sweep(state.mixes, managers=state.managers,
                     total_ms=state.total_ms)


def setup(ctx) -> State:
    from bench import traffic
    from reference.cmp import MANAGER_NAMES, apps

    named = traffic.mixes(ctx.traffic, ctx.seed, apps.ABBREV)
    state = State(names=[n for n, _ in named], mixes=[m for _, m in named],
                  managers=list(ctx.config.get("managers")
                                or MANAGER_NAMES),
                  total_ms=float(ctx.config["total_ms"]))
    _sweep(state)   # compiles (or loads) every program the window runs
    return state


def window(state: State, seconds: float, ctx) -> Dict:
    import jax

    from repro.core.dispatch import device_dispatches, reset_device_dispatches

    walls, results = [], []
    reset_device_dispatches()
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.sweep"):
            res = _sweep(state)
        walls.append(time.perf_counter() - t)
        results.append(({m: np.asarray(res.ipc.get(m, ())) for m in state.managers},
                        np.asarray(res.baseline_ipc)))
        if time.perf_counter() >= deadline:
            break
    end = time.perf_counter()
    return {"kind": "sweep", "walls": walls, "window_s": end - start,
            "sweeps": len(walls), "batches": len(walls),
            "mixes_per_sweep": len(state.mixes),
            "dispatches": device_dispatches(), "results": results}


def end_to_end(record: Dict, ctx) -> Dict[str, float]:
    walls = record["walls"]
    out = {"sweep_mixes_per_s": record["sweeps"] * record["mixes_per_sweep"]
           / record["window_s"]}
    if len(walls) >= 2:
        out["sweep_s_p90"] = statistics.quantiles(walls, n=10)[8]
    return out


def release(state: State) -> None:
    """The sweep keeps no device state between calls."""


def _rel(rows, i: int, ref: np.ndarray) -> float:
    """Largest relative error of row ``i`` of the program's output; a row
    that is missing, misshapen or not finite reads as infinite."""
    rows = np.asarray(rows)
    if rows.ndim < 1 or i >= rows.shape[0] or rows[i].shape != ref.shape:
        return float("inf")
    err = float(np.max(np.abs(rows[i] - ref) / np.abs(ref)))
    return err if np.isfinite(err) else float("inf")


def reference_sweep(mixes, managers, total_ms, dtype=np.float64):
    """What ``run_sweep`` returns, from the plain reference: per manager the
    ``(mixes, apps)`` IPC, and the baseline's.  ``dtype`` is the precision
    of the reference's interval model (float32 for the control)."""
    from reference.cmp import golden, memsys

    old = memsys.DTYPE
    memsys.DTYPE = dtype
    try:
        ref = [golden(mix, total_ms, managers) for mix in mixes]
    finally:
        memsys.DTYPE = old
    return types.SimpleNamespace(
        ipc={m: np.stack([r[m] for r in ref]) for m in managers},
        baseline_ipc=np.stack([r["__baseline__"] for r in ref]))


def check(state: State, record: Dict, ctx):
    """Every sweep of the window against the reference, per mix: the
    baseline IPC and every manager's per-app IPC, as relative errors."""
    lim = ctx.settings["limits"]
    ref = reference_sweep(state.mixes, state.managers, state.total_ms)
    worst_base = worst_ipc = 0.0
    failed = 0
    for ipc, base in record["results"]:
        for i in range(len(state.mixes)):
            eb = _rel(base, i, ref.baseline_ipc[i])
            ei = max(_rel(ipc.get(m, []), i, ref.ipc[m][i])
                     for m in state.managers)
            worst_base, worst_ipc = max(worst_base, eb), max(worst_ipc, ei)
            failed += (eb > lim["baseline_rel_err"]
                       or ei > lim["ipc_rel_err"])
    checks = [
        {"name": "baseline_rel_err", "value": worst_base,
         "limit": lim["baseline_rel_err"]},
        {"name": "ipc_rel_err", "value": worst_ipc,
         "limit": lim["ipc_rel_err"]},
    ]
    attempted = record["sweeps"] * record["mixes_per_sweep"]
    return checks, attempted, failed


def control(state: State, ctx) -> Dict:
    """The precision control in the program's place: a window's record
    holding one sweep computed by the reference's interval model in
    float32, for :func:`check` to judge."""
    low = reference_sweep(state.mixes, state.managers, state.total_ms,
                          np.float32)
    return {"kind": "sweep", "sweeps": 1, "batches": 1,
            "mixes_per_sweep": len(state.mixes),
            "results": [(low.ipc, low.baseline_ipc)]}
