"""CPU tests of the reduction by the program's own spans and scopes
(``bench/scopes.py``) and of the per-layer readers of the program's span
totals and counters."""
from __future__ import annotations

import pathlib

import pytest

import chip_bench_util as u
from bench import harness, scopes, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY = str(DATA / "tiny_trace.xplane.pb")


def _ev(name, start, dur):
    return trace.Event(name, float(start), float(dur))


# --------------------------------------------------- xplane metadata


def test_op_names_of_the_recorded_tpu_trace():
    names = scopes.op_names(TINY)["/device:TPU:0"]
    by_op = {name.split(" ")[0]: tf_op for (_pid, name), tf_op in names.items()}
    assert by_op["%fusion"] == "jit(<lambda>)/dot_general:"
    assert by_op["%multiply_reduce_fusion"] == "jit(<lambda>)/reduce_sum:"
    # each op is keyed by the program it belongs to, as the XLA Modules
    # line names it
    modules = trace.load(TINY)[1].lines["XLA Modules"]
    ids = {int(e.name.rsplit("(", 1)[1][:-1]) for e in modules}
    assert {pid for pid, _ in names} <= ids


def test_tiny_trace_summary_reads_what_it_read():
    """The fields of ``bench.trace.Summary`` keep their definitions."""
    s = trace.summarize(trace.load(TINY))
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(7.6646e-05)
    assert s.module_s == pytest.approx({"jit__lambda": 7.6672e-05})
    assert s.module_calls == {"jit__lambda": 6}
    assert len(s.op_s) == 4
    assert sum(s.op_s.values()) == pytest.approx(7.6646e-05)
    assert s.gaps[:3] == [("bench.host", pytest.approx(0.004904663)),
                          ("bench.host", pytest.approx(0.004828195)),
                          ("bench.host", pytest.approx(0.004808272))]
    assert trace.top_ops(s, 1)[0][0].startswith("%fusion = ")


def test_tiny_trace_layers():
    summary, layers = scopes.load(TINY)
    assert layers.module_calls == 6
    assert layers.scope_self_s == {}          # no cbp.* scope in it
    assert layers.span_s == {} and layers.span_idle_s == {}
    assert layers.gaps[0] == summary.gaps[0]  # named by bench.host alike


# ----------------------------------------------------------- self time


def _nested():
    """A while holding a conditional holding a fusion, a second fusion in
    the while, and the baseline program after it."""
    ops = [_ev("while", 0, 1000), _ev("cond", 100, 600),
           _ev("fusion", 200, 300), _ev("fusion.2", 800, 100),
           _ev("div", 1100, 100)]
    modules = [_ev("jit_fn(7)", 0, 1000), _ev("jit__evaluate_jit(9)", 1100, 100)]
    names = {(7, "while"): "jit(fn)/while",
             (7, "cond"): "jit(fn)/while/body/cbp.greedy/cond",
             (7, "fusion"): "jit(fn)/while/body/cbp.greedy/while/body/add",
             (7, "fusion.2"): "jit(fn)/while/body/cbp.interval/mul",
             (9, "div"): "jit(_evaluate_jit)/cbp.interval/div"}
    return trace.Plane("/device:TPU:0", {"XLA Ops": ops,
                                         "XLA Modules": modules}), names


def test_self_time_subtracts_the_ops_nested_inside():
    dev, _ = _nested()
    assert scopes.self_times(dev.lines["XLA Ops"]) == [300, 300, 300, 100,
                                                       100]


def test_scope_self_time_by_innermost_scope():
    dev, names = _nested()
    got = scopes.scope_self_s(dev, names)
    assert got == pytest.approx({"cbp.greedy": 600e-9,
                                 "cbp.interval": 200e-9})
    assert scopes.scope_of("a/cbp.greedy/b/cbp.interval/c") == "cbp.interval"
    assert scopes.scope_of("jit(fn)/while") is None
    # an op of a program the metadata does not know is in no scope
    assert scopes.scope_self_s(dev, {}) == {}


# ------------------------------------------------ host spans and gaps


def _spanned():
    host = trace.Plane("/host:CPU", {"python": [
        _ev("bench.sweep", 0, 1100),
        _ev("cbp.sweep.prepare", 0, 350),
        _ev("cbp.sweep.collect", 500, 400),
        _ev("cbp.sweep.collect", 1200, 100),
        _ev("other.span", 600, 10),
    ]})
    dev = trace.Plane("/device:TPU:0", {
        "XLA Ops": [_ev("a", 100, 100), _ev("b", 400, 200),
                    _ev("c", 700, 50), _ev("d", 1250, 10)],
        "XLA Modules": [_ev("jit_fn(7)", 100, 650),
                        _ev("jit_x(8)", 1250, 10)],
    })
    return [host, dev, trace.Plane("/host:metadata", {})]


def test_spans_summed_by_name_with_the_idle_inside():
    got = scopes.layers(_spanned(), {})
    assert got.span_s == pytest.approx({"cbp.sweep.prepare": 350e-9,
                                        "cbp.sweep.collect": 500e-9})
    assert got.span_calls == {"cbp.sweep.prepare": 1,
                              "cbp.sweep.collect": 2}
    # prepare [0, 350]: busy [100, 200] -> idle 250; collect [500, 900]:
    # busy [500, 600] and [700, 750] -> idle 250, [1200, 1300]: busy 10
    # -> idle 90.
    assert got.span_idle_s == pytest.approx({"cbp.sweep.prepare": 250e-9,
                                             "cbp.sweep.collect": 340e-9})
    assert got.module_calls == 2


def test_gaps_named_by_the_innermost_bench_or_program_span():
    got = scopes.layers(_spanned(), {})
    # gaps (750, 1250) mid 1000: bench.sweep only; (200, 400) mid 300:
    # inside cbp.sweep.prepare; (600, 700) mid 650: cbp.sweep.collect.
    assert [n for n, _ in got.gaps] == ["bench.sweep", "cbp.sweep.prepare",
                                        "cbp.sweep.collect"]
    assert [g for _, g in got.gaps] == pytest.approx([500e-9, 200e-9,
                                                      100e-9])


def test_layers_need_a_device_plane():
    with pytest.raises(ValueError):
        scopes.layers([_spanned()[0]], {})


# ----------------------------------------------------- metric readers


def _metric(name):
    return harness.load_module(u.BENCH / "metrics" / f"{name}.py",
                               "bench_metric_test_" + name.replace(".", "_"))


SWEEPS = {"kind": "sweep", "sweeps": 4, "batches": 4}


@pytest.fixture
def window():
    from repro.core import dispatch

    dispatch.reset_device_dispatches()
    yield dispatch
    dispatch.reset_device_dispatches()


def test_program_span_readers(window):
    for _ in range(4):
        with window.span("cbp.sweep.prepare"):
            pass
        with window.span("cbp.sweep.collect"):
            pass
    totals = window.span_seconds()
    for name in ("prepare", "collect"):
        read = _metric(f"sim.{name}_ms").read
        assert read(SWEEPS, None, None) == pytest.approx(
            1e3 * totals[f"cbp.sweep.{name}"] / 4)
        assert read({"kind": "other"}, None, None) is None
        assert read(dict(SWEEPS, sweeps=0), None, None) is None
    window.reset_device_dispatches()
    assert _metric("sim.prepare_ms").read(SWEEPS, None, None) is None


def test_greedy_trips_reader(window):
    window.record_greedy_trips(96)
    read = _metric("sim.greedy_trips").read
    assert read(SWEEPS, None, None) == 24.0
    assert read({"kind": "other"}, None, None) is None


def test_readers_of_a_program_without_spans_or_counter(window, monkeypatch):
    """The parent program has neither: its readers read nothing and do not
    raise."""
    monkeypatch.delattr(window, "span_seconds")
    monkeypatch.delattr(window, "greedy_trips")
    for name in ("sim.prepare_ms", "sim.collect_ms", "sim.greedy_trips"):
        assert _metric(name).read(SWEEPS, None, None) is None


def test_device_programs_per_sweep_reader():
    read = _metric("sim.device_programs_per_sweep").read
    s = trace.Summary(n_devices=2, busy_s=1.0, module_s={},
                      module_calls={"jit_fn": 8, "jit_slice": 280}, op_s={},
                      gaps=[])
    assert read(SWEEPS, s, None) == pytest.approx(288 / 2 / 4)
    assert read(SWEEPS, None, None) is None
    assert read({"kind": "other"}, s, None) is None


def test_every_per_layer_metric_has_a_reader():
    import json

    man = json.loads((u.REPO / "BENCHMARK.json").read_text())
    for m in man["per_layer"]:
        assert callable(_metric(m["name"]).read), m["name"]


# ------------------------------------------------ a recorded sweep trace


def test_recorded_scoped_sweep_trace():
    """A TPU v5e trace of one warm ``run_sweep`` over Table 2's w1 and w2
    under the managers ``baseline`` and ``only cache``, 12 ms simulated
    (one reconfiguration boundary), inside a ``bench.sweep`` span.  It was
    pruned to what the reductions read: the device's XLA Ops and XLA
    Modules lines with each op's name, ``tf_op`` and ``program_id``, and
    the host's ``bench.*`` and ``cbp.*`` spans."""
    path = str(DATA / "scoped_trace.xplane.pb")
    planes = trace.load(path)
    summary, layers = trace.summarize(planes), scopes.layers(
        planes, scopes.op_names(path))
    scope = layers.scope_self_s
    assert scope["cbp.interval"] > 0 and scope["cbp.greedy"] > 0
    # the interval model also runs in the baseline program
    assert scope["cbp.interval"] + scope["cbp.greedy"] <= (
        summary.module_s["jit_fn"] + summary.module_s["jit__evaluate_jit"])
    # inside the scan alone, both together fit in the scan's time
    dev = trace.device_planes(planes)[0]
    scan = trace.Plane(dev.name, {
        "XLA Ops": dev.lines["XLA Ops"],
        "XLA Modules": [e for e in dev.lines["XLA Modules"]
                        if trace.module_name(e.name) == "jit_fn"]})
    in_scan = scopes.scope_self_s(scan, scopes.op_names(path)[dev.name])
    assert in_scan["cbp.interval"] > 0 and in_scan["cbp.greedy"] > 0
    assert sum(in_scan.values()) <= summary.module_s["jit_fn"]
    assert layers.span_calls == {"cbp.sweep.prepare": 1,
                                 "cbp.sweep.collect": 1,
                                 "cbp.sweep.baseline": 1}
    assert 0 < layers.span_idle_s["cbp.sweep.collect"] <= (
        layers.span_s["cbp.sweep.collect"])
    assert layers.gaps and all(name.startswith(("bench.", "cbp."))
                               for name, _ in layers.gaps)
