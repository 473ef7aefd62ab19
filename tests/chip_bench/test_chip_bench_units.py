"""CPU tests of the chip benchmark's yardstick: the trace reduction, the
per-layer metric readers, the traffic generator and the peaks table."""
from __future__ import annotations

import json
import pathlib

import pytest

import chip_bench_util as u
from bench import harness, peaks, trace, traffic

DATA = pathlib.Path(__file__).resolve().parent / "data"
TABLE2 = json.loads((u.BENCH / "traffic" / "table2.json").read_text())


# ------------------------------------------------------------- trace


def _ev(name, start, dur):
    return trace.Event(name, float(start), float(dur))


def _planes():
    host = trace.Plane("/host:CPU", {"python": [
        _ev("bench.window", 0, 1000),
        _ev("bench.sweep", 0, 400),
        _ev("bench.fetch", 250, 100),
        _ev("other.span", 500, 10),
    ]})
    dev0 = trace.Plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion.1", 0, 100), _ev("fusion.2", 50, 150),
                    _ev("copy.3", 300, 20), _ev("fusion.1", 700, 100)],
        "XLA Modules": [_ev("jit_scan(12)", 0, 200),
                        _ev("jit_scan(12)", 700, 100),
                        _ev("jit_base(3)", 300, 20)],
    })
    dev1 = trace.Plane("/device:TPU:1", {
        "XLA Ops": [_ev("fusion.1", 0, 400)],
        "XLA Modules": [_ev("jit_scan(12)", 0, 400)],
    })
    return [host, dev0, dev1, trace.Plane("/host:metadata", {})]


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_summary_busy_modules_and_ops():
    s = trace.summarize(_planes())
    assert s.n_devices == 2
    # device 0 busy [0, 200] + [300, 320] + [700, 800] = 320 ns; device 1
    # busy 400 ns; the mean over the devices.
    assert s.busy_s == pytest.approx(360e-9)
    assert s.module_s == pytest.approx({"jit_scan": 700e-9,
                                        "jit_base": 20e-9})
    assert s.module_calls == {"jit_scan": 3, "jit_base": 1}
    assert s.op_s["fusion.1"] == pytest.approx(600e-9)
    assert trace.top_ops(s, 1) == [["fusion.1", pytest.approx(600e-9)]]


def test_gaps_longest_first_named_by_innermost_span():
    s = trace.summarize(_planes())
    # gaps of device 0: (200, 300) mid 250 -> inside bench.sweep and
    # bench.fetch starts at 250: innermost is bench.fetch; (320, 700) mid
    # 510 -> only bench.window (other.* is not a bench span).
    assert [name for name, _ in s.gaps] == ["bench.window", "bench.fetch"]
    assert [g for _, g in s.gaps] == pytest.approx([380e-9, 100e-9])


@pytest.mark.parametrize("device_end_s, cut", [(9.9, False), (5.0, True)])
def test_summary_flags_device_events_that_stop_early(device_end_s, cut):
    """The device events' span, and a cut when they stop well before the
    benchmark's host spans end (a trace that kept only its first 5 s)."""
    host = trace.Plane("/host:CPU", {"python": [
        _ev("bench.window", 0, 10e9), _ev("bench.call", 1e9, 8e9)]})
    dev = trace.Plane("/device:TPU:0", {
        "XLA Ops": [_ev("fusion.1", 0.5e9, 1e9),
                    _ev("fusion.2", device_end_s * 1e9 - 1e9, 1e9)],
        "XLA Modules": [_ev("jit_step(1)", 0.5e9, device_end_s * 1e9
                            - 0.5e9)]})
    s = trace.summarize([host, dev])
    assert s.span_s == pytest.approx(device_end_s - 0.5)
    assert s.cut is cut


def test_summary_needs_a_device_plane():
    with pytest.raises(ValueError):
        trace.summarize([_planes()[0]])


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e: three calls of two small jitted
    programs with a host-only span between them."""
    s = trace.summarize(trace.load(str(DATA / "tiny_trace.xplane.pb")))
    assert s.n_devices == 1
    assert 0 < s.busy_s < 1.0
    assert sum(s.module_calls.values()) == 6
    assert s.gaps and s.gaps[0][1] > 0
    assert any(name == "bench.host" for name, _ in s.gaps)


def test_find_xplane_picks_newest(tmp_path):
    a = tmp_path / "plugins" / "profile" / "1" / "h.xplane.pb"
    b = tmp_path / "plugins" / "profile" / "2" / "h.xplane.pb"
    for p in (a, b):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
    import os
    os.utime(a, (1, 1))
    assert trace.find_xplane(str(tmp_path)) == str(b)
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path / "none"))


# ----------------------------------------------------- metric readers


def _metric(name):
    return harness.load_module(u.BENCH / "metrics" / f"{name}.py",
                               "bench_metric_test_" + name.replace(".", "_"))


def _summary(module_s, n_devices=1, busy_s=3.0):
    return trace.Summary(busy_s=busy_s, n_devices=n_devices,
                         module_s=module_s, module_calls={}, op_s={},
                         gaps=[])


SWEEPS = {"kind": "sweep", "batches": 4, "dispatches": 8}
CTX = type("Ctx", (), {"window_s": 4.0})()


def test_scan_device_ms_reads_the_scan_module_by_name(capsys):
    read = _metric("sim.scan_device_ms").read
    # the scan is read by its name, not as the heaviest module
    s = _summary({"jit_fn": 2.0, "jit__evaluate_jit": 6.0}, n_devices=2)
    assert read(SWEEPS, s, CTX) == pytest.approx(1e3 * 2.0 / 2 / 4)
    # renamed or split: nothing to read, and it says so
    assert read(SWEEPS, _summary({"jit_other": 6.0}), CTX) is None
    assert "jit_fn" in capsys.readouterr().err
    assert read(SWEEPS, None, CTX) is None
    assert read({"kind": "other"}, s, CTX) is None


def test_idle_share_and_programs_per_batch():
    idle = _metric("sim.idle_share").read
    assert idle(SWEEPS, _summary({}, busy_s=3.0), CTX) == pytest.approx(25.0)
    assert idle(SWEEPS, None, CTX) is None
    per = _metric("sim.programs_per_batch").read
    assert per(SWEEPS, None, CTX) == 2.0
    assert per(dict(SWEEPS, batches=0), None, CTX) is None


# ----------------------------------------------------------- traffic


def test_mixes_order_follows_seed():
    from reference.cmp import apps

    a = traffic.mixes(TABLE2, 5, apps.ABBREV)
    b = traffic.mixes(TABLE2, 6, apps.ABBREV)
    assert sorted(n for n, _ in a) == sorted(TABLE2["mixes"])
    assert [n for n, _ in a] != [n for n, _ in b]
    assert all(len(m) == 16 for _, m in a)
    assert dict(a) == dict(b)


# ------------------------------------------------------------- peaks


def test_peaks_lookup():
    p = peaks.lookup("TPU v5 lite")
    assert p.flops_bf16 == 197e12 and p.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


def test_copied_traffic_matches_the_program_today():
    """The benchmark keeps its own copy of the Table-2 mixes; today it
    agrees with the program's."""
    from reference.cmp import apps

    from repro.sim import WORKLOADS

    assert dict(traffic.mixes(TABLE2, 0, apps.ABBREV)) == WORKLOADS
