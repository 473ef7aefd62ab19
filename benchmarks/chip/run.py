"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <run_seconds> --trace <0|1>

Loads the cell's configuration, traffic and entry from their own files
(see ``bench/harness.py``), warms up the cell's shapes, measures for
``--seconds`` seconds (``--trace 1``: traces a shorter window and reports
the per-layer metrics instead), checks the timed path's output against the
plain reference, and prints one JSON line.  Without an accelerator, or with
fewer chips than the cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    harness.prepare_env(ROOT)
    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T0)
    except (harness.BenchError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
