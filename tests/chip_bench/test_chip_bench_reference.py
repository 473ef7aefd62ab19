"""CPU tests of the chip benchmark's plain reference of the CMP evaluator
and its precision control, at sizes a test run can hold."""
from __future__ import annotations

import numpy as np

import chip_bench_util as u
from bench import harness, traffic
from reference.cmp import apps


def test_cmp_reference_copy_equals_the_program_golden():
    from reference.cmp import golden

    from repro.sim import WORKLOADS, baseline_ipc, run_all_managers

    mix = WORKLOADS["w5"]
    ref = golden(mix, 20.0, ["baseline", "CBP", "CPpf", "qos"])
    prog = run_all_managers(mix, total_ms=20.0,
                            names=["baseline", "CBP", "CPpf", "qos"])
    for m in prog:
        np.testing.assert_array_equal(ref[m], prog[m].ipc)
    np.testing.assert_array_equal(ref["__baseline__"], baseline_ipc(mix))


def test_cmp_float32_control_fails_the_limit():
    """The control departs from the float64 reference by more than the
    cell's limit, with room to spare."""
    entry = harness.load_module(u.BENCH / "entries" / "sweep.py",
                                "bench_entry_sweep_reference")
    mixes = [traffic.parse_mix(spec, apps.ABBREV)
             for spec in u.TINY_MIXES["mixes"].values()]
    ref = entry.reference_sweep(mixes, ["CBP"], 20.0)
    low = entry.reference_sweep(mixes, ["CBP"], 20.0, np.float32)
    err = np.max(np.abs(low.baseline_ipc - ref.baseline_ipc)
                 / ref.baseline_ipc)
    assert err > 10 * u.TINY_SWEEP["limits"]["baseline_rel_err"]
