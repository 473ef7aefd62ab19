"""Readings that a cell's correctness limits are set from, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> \\
      --seeds 101,102,... --seconds <short window> [--control-seeds 3]

For each seed, in one process: the cell's set-up, a short window at the
cell's own size and load, then the numbers the run compares (the program's
readings) and, on the first ``--control-seeds`` seeds, the same numbers
for the precision control: the entry's ``control`` (the reference in the
precision below the one the configuration states) put in the program's
place and judged by the entry's own ``check`` against the cell's limits.
One JSON line per seed, with ``correct`` for both; the benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    harness.prepare_env(ROOT)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx, _man, entry = harness.prepare(ROOT, args.workload, seed, False)
        state = entry.setup(ctx)
        record = entry.window(state, args.seconds, ctx)
        entry.release(state)
        gc.collect()
        checks, attempted, failed = entry.check(state, record, ctx)
        row = {"seed": seed, "attempted": int(attempted),
               "failed": int(failed), "correct": harness.verdict(checks),
               "program": {c["name"]: c["value"] for c in checks}}
        if i < args.control_seeds:
            ctl, _, _ = entry.check(state, entry.control(state, ctx), ctx)
            row["control_correct"] = harness.verdict(ctl)
            row["control"] = {c["name"]: c["value"] for c in ctl}
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del state, record
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
