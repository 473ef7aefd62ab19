"""Split a sweep cell's window by the program's own spans and scopes.

  python3 benchmarks/chip/layers.py --workload sweep-table2 --seed <n> \\
      --seconds <s>

Sets the cell up as ``run.py`` does, then runs two windows of
``--seconds`` each: one untraced and one under the profiler.  Prints one
JSON line: both windows' sweep walls (what tracing costs when on), and,
per sweep of the traced window, the program's ``cbp.*`` host spans and
the device idle inside each, the device self time under each ``cbp.*``
scope (``bench/scopes.py``), the scan module's device time, the device
programs against the program's dispatch counter, the greedy trips, and the
longest idle gaps named by the innermost ``bench.*`` or ``cbp.*`` span.
It checks nothing against the reference: ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import statistics
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SCAN_MODULE = "jit_fn"


def _walls(record):
    walls = record["walls"]
    return {"sweeps": len(walls), "median_s": statistics.median(walls),
            "p90_s": (statistics.quantiles(walls, n=10)[8]
                      if len(walls) >= 2 else walls[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="sweep-table2")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    harness.prepare_env(ROOT)
    import jax

    from bench import scopes, trace
    from repro.core import dispatch

    ctx, _man, entry = harness.prepare(ROOT, args.workload, args.seed, True)
    state = entry.setup(ctx)
    plain = entry.window(state, args.seconds, ctx)
    path = ROOT / harness.TRACE_DIR / f"{args.workload}-layers"
    shutil.rmtree(path, ignore_errors=True)
    jax.profiler.start_trace(str(path))
    try:
        traced = entry.window(state, args.seconds, ctx)
    finally:
        jax.profiler.stop_trace()
    trips, dispatches = dispatch.greedy_trips(), dispatch.device_dispatches()
    program_span_s = dispatch.span_seconds()
    xplane = trace.find_xplane(str(path))
    summary, layers = scopes.load(xplane)
    shutil.rmtree(path, ignore_errors=True)
    entry.release(state)

    n = traced["sweeps"]
    out = {
        "workload": args.workload, "seed": args.seed,
        "untraced": _walls(plain), "traced": _walls(traced),
        "window_s": traced["window_s"],
        "idle_share": 100.0 * (1.0 - summary.busy_s / traced["window_s"]),
        "span_ms": {k: 1e3 * v / n for k, v in layers.span_s.items()},
        "program_span_ms": {k: 1e3 * v / n
                            for k, v in program_span_s.items()},
        "span_calls": {k: v / n for k, v in layers.span_calls.items()},
        "span_idle_ms": {k: 1e3 * v / n
                         for k, v in layers.span_idle_s.items()},
        "scope_device_ms": {k: 1e3 * v / n
                            for k, v in layers.scope_self_s.items()},
        "module_device_ms": {k: 1e3 * v / summary.n_devices / n
                             for k, v in summary.module_s.items()},
        "scan_module": SCAN_MODULE,
        "device_programs_per_sweep": layers.module_calls / n,
        "programs_per_batch": dispatches / n,
        "greedy_trips_per_sweep": trips / n,
        "gaps": layers.gaps,
    }
    out["tracing_cost"] = (out["traced"]["median_s"]
                           / out["untraced"]["median_s"] - 1.0)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
