"""Benchmark harness — one entry per paper table/figure + the roofline and
kernel benches.  Prints ``name,us_per_call,derived`` CSV rows.

Each bench runs under a wall timeout (``--bench-timeout``, SIGALRM): a
hung bench fails with a named culprit instead of stalling the whole
harness until the CI job's global timeout reaps it anonymously.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
      [--bench-timeout SECONDS]
"""
from __future__ import annotations

import argparse
import signal
import sys


class BenchTimeout(RuntimeError):
    """A bench exceeded its wall budget."""


def _run_with_timeout(name: str, fn, seconds: int) -> None:
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        fn()
        return

    def _alarm(signum, frame):
        raise BenchTimeout(
            f"bench {name!r} exceeded its {seconds}s wall timeout")

    prev_handler = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        fn()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev_handler)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="subsample fig5's 640 workloads to 64")
    ap.add_argument("--only", default=None)
    ap.add_argument("--bench-timeout", type=int, default=1800,
                    help="per-bench wall timeout in seconds "
                         "(0 disables; default 1800)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (
        fig5_smoke,
        kernel_bench,
        paper_figs,
        roofline_report,
        runtime_bench,
        scenario_report,
        serving_bench,
        stream_bench,
    )

    benches = {
        "fig1": paper_figs.fig1_motivation,
        "fig2": paper_figs.fig2_characterization,
        "fig3": paper_figs.fig3_prefetch_alloc,
        "fig4": paper_figs.fig4_leslie3d,
        # fig5 runs on the batched static-search subsystem (one device
        # program per manager family — repro.sim.static_search).
        "fig5": (lambda: paper_figs.fig5_potential(
            64 if args.quick else 640)),
        "fig5_smoke": fig5_smoke.main,
        # serving engine: device-resident continuous batching vs the host
        # loop; --quick runs the CI smoke shape, default the 256-4096
        # slot sweep with the >= 5x acceptance gate at >= 256 slots.
        "serving_bench": (lambda: serving_bench.main(
            serving_bench.SMOKE_SLOTS if args.quick
            else serving_bench.DEFAULT_SLOTS,
            groups=1, smoke=args.quick, compare_host_all=False)),
        # streaming sweep service: --quick runs the CI smoke (resume
        # parity + dispatch budget), default the 10^5-mix scale record.
        "stream_bench": (lambda: stream_bench.main(smoke_mode=args.quick)),
        # runtime bindings: fused TrainingPlant one-dispatch + bit-parity
        # vs the host coordinator, batched block-planner parity; default
        # adds the 400 ms / 12-client scale record.
        "runtime_bench": (lambda: runtime_bench.main(smoke_mode=args.quick)),
        "fig9_10": paper_figs.fig9_fig10_main,
        "fig11": paper_figs.fig11_case_study,
        "fig12": paper_figs.fig12_sensitivity,
        "scenario_diversity": (lambda: scenario_report.scenario_diversity(
            8 if args.quick else 32)),
        "kernel_flash_attention": kernel_bench.flash_attention_bench,
        "kernel_flash_decode": kernel_bench.flash_decode_bench,
        "kernel_ssd_scan": kernel_bench.ssd_scan_bench,
        "kernel_cbp_matmul": kernel_bench.cbp_matmul_knob_sweep,
        "kernel_blocks": kernel_bench.kernel_block_plan_bench,
        "kernel_lookahead": kernel_bench.lookahead_bench,
        "roofline": roofline_report.roofline_report,
    }
    selected = {name: fn for name, fn in benches.items()
                if not args.only or args.only in name}
    if not selected:
        # A typo'd --only used to print the CSV header and exit 0 — green
        # CI with zero benches run.  Fail loudly with the valid names.
        sys.exit(f"--only {args.only!r} matches no bench; known benches: "
                 + ", ".join(benches))
    failed = []
    print("name,us_per_call,derived")
    for name, fn in selected.items():
        try:
            _run_with_timeout(name, fn, args.bench_timeout)
        except Exception as exc:  # noqa: BLE001
            failed.append(name)
            print(f"{name},0,ERROR={type(exc).__name__}:{exc}",
                  flush=True)
    if failed:
        # The ERROR rows keep the CSV parseable, but a broken bench must
        # not exit 0 — CI reads the exit code, not the rows.
        sys.exit(f"{len(failed)} bench(es) errored: {', '.join(failed)}")


if __name__ == "__main__":
    main()
