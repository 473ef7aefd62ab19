"""The data-driven harness behind ``run.py``.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json`` (repo root): the cell's configuration, traffic, chips,
  and which end-to-end and per-layer metrics it reports;
* ``configs/<config>.json``: the configuration as it is run;
* ``traffic/<traffic>.json``: the traffic parameters (``bench/traffic.py``);
* ``workloads/<cell>.json``: the entry kind that drives the program, its
  settings, and the limits of the correctness comparison;
* ``entries/<entry>.py``: the code that drives one kind of entry point;
* ``metrics/<metric>.py``: the reader of one per-layer metric.

An entry module provides ``setup(ctx)``, ``window(state, seconds, ctx)``,
``end_to_end(record, ctx)``, ``release(state)``,
``check(state, record, ctx)`` and, for ``calibrate.py``,
``control(state, ctx)``: a window's record computed by the reference in
the precision below the configuration's.  A metric module provides
``read(record, trace, ctx)``, which returns ``None`` where it finds
nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parents[1]      # benchmarks/chip
TRACE_DIR = ".bench_trace"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a missing file)."""


@dataclasses.dataclass
class Context:
    root: pathlib.Path
    cell_name: str
    cell: Dict[str, Any]          # the BENCHMARK.json workload entry
    settings: Dict[str, Any]      # workloads/<cell>.json
    config: Dict[str, Any]        # configs/<config>.json
    traffic: Dict[str, Any]       # traffic/<traffic>.json
    seed: int
    trace: bool
    devices: List[Any]
    peaks: Any = None
    compiles: Any = None
    window_s: float = 0.0         # the traced window, with --trace 1


class CompileLog:
    """Counts JAX's compile events and their seconds, by JAX's own
    ``/jax/core/compile/`` duration events."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/backend_compile"):
            self.count += 1
        if name.startswith("/jax/core/compile/"):
            self.seconds += float(secs)


def load_json(path: pathlib.Path) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing benchmark file {path}") from None


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def manifest(root: pathlib.Path) -> Dict:
    return load_json(root / "BENCHMARK.json")


def _for_cell(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_files(root: pathlib.Path, bench_dir: pathlib.Path, cell_name: str):
    man = manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if cell_name not in cells:
        raise BenchError(f"no workload {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in man["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    settings = load_json(bench_dir / "workloads" / f"{cell_name}.json")
    return man, cell, config, traffic, settings


def prepare_env(root: pathlib.Path) -> str:
    """Call before importing jax.  JAX's persistent cache goes to a fixed
    path inside the checkout (every program, however quick to compile),
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; the TPU runtime's own
    log files, which would go to a fixed path under ``/tmp``, are off.
    Returns the cache directory."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(root / ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return path


def require_chips(n: int):
    """The first ``n`` accelerator devices; no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise BenchError("no accelerator: JAX found only the CPU")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


@contextlib.contextmanager
def _profiled(path: pathlib.Path):
    import jax

    shutil.rmtree(path, ignore_errors=True)
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def prepare(root: pathlib.Path, cell_name: str, seed: int, trace: bool,
            bench_dir: Optional[pathlib.Path] = None, devices=None):
    """The cell's context, the manifest and the entry module; takes the
    chips (``devices`` is for tests, which pass the CPU) and starts
    counting compilations."""
    bench_dir = bench_dir or HERE
    man, cell, config, traffic, settings = cell_files(root, bench_dir,
                                                      cell_name)
    import jax

    from bench import peaks as peaks_mod

    devs = devices if devices is not None else require_chips(cell["chips"])
    peaks = peaks_mod.lookup(devs[0].device_kind) if devices is None else None
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    ctx = Context(root=root, cell_name=cell_name, cell=cell,
                  settings=settings, config=config, traffic=traffic,
                  seed=int(seed), trace=bool(trace), devices=devs,
                  peaks=peaks, compiles=log)
    entry = load_module(bench_dir / "entries" / f"{settings['entry']}.py",
                        f"bench_entry_{settings['entry']}")
    return ctx, man, entry


def verdict(checks: List[Dict]) -> bool:
    """``correct``: every compared number at or under its limit.  A value
    that is not finite (a missing answer) is set to 1e300, since JSON has
    no infinity."""
    for c in checks:
        c["value"] = float(c["value"]) if math.isfinite(c["value"]) else 1e300
    return all(c["value"] <= c["limit"] for c in checks)


def run_cell(root: pathlib.Path, cell_name: str, seed: int, seconds: float,
             trace: bool, t0: float, bench_dir: Optional[pathlib.Path] = None,
             devices=None) -> Dict:
    """One run of one cell; returns the result object.

    ``devices`` is for tests, which drive the harness on the CPU; a run
    from ``run.py`` always takes the chips from :func:`require_chips`.
    """
    import jax

    from bench import trace as trace_mod

    bench_dir = bench_dir or HERE
    ctx, man, entry = prepare(root, cell_name, seed, trace, bench_dir,
                              devices)
    devs, log = ctx.devices, ctx.compiles

    state = entry.setup(ctx)
    setup_s = time.perf_counter() - t0
    compile_setup_s, compiles_setup = log.seconds, log.count

    trace_path = root / TRACE_DIR / cell_name
    summary, window_s = None, None
    if trace:
        span = min(float(seconds), float(ctx.settings.get("trace_seconds",
                                                          seconds)))
        with _profiled(trace_path):
            w0 = time.perf_counter()
            record = entry.window(state, span, ctx)
            window_s = time.perf_counter() - w0
        ctx.window_s = window_s
        summary = trace_mod.summarize(trace_mod.load(
            trace_mod.find_xplane(str(trace_path))))
        shutil.rmtree(trace_path, ignore_errors=True)
        if summary.cut:
            print(f"run.py: the trace's device events stop "
                  f"{window_s - summary.span_s:.2f} s short of the "
                  f"{window_s:.2f} s window: its readings are short; "
                  f"lower trace_seconds in workloads/{cell_name}.json",
                  file=sys.stderr)
    else:
        record = entry.window(state, float(seconds), ctx)
    compiles_window = log.count - compiles_setup
    record["setup_compile_s"] = compile_setup_s
    peak = _peak_bytes(devs)

    entry.release(state)
    gc.collect()
    checks, attempted, failed = entry.check(state, record, ctx)
    correct = verdict(checks)

    metrics: Dict[str, Dict] = {}
    if trace:
        for m in man["per_layer"]:
            if not _for_cell(m, cell_name):
                continue
            reader = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(record, summary, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        e2e = entry.end_to_end(record, ctx)
        e2e["setup_s"] = setup_s
        for m in man["end_to_end"]:
            if _for_cell(m, cell_name) and m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
        "window": {"compiles_in_window": compiles_window,
                   "compiles_in_setup": compiles_setup,
                   "setup_s": setup_s, "seconds": record["window_s"]},
    }
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = window_s
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(summary),
            "idle_gaps": [[n, s] for n, s in summary.gaps],
        }
    result["checks"] = checks
    return result


def emit(result: Dict, out=None, err=None) -> None:
    """The result line last on stdout; each compared number beside its
    limit as the last lines on stderr."""
    out = out or sys.stdout
    err = err or sys.stderr
    out.write(json.dumps(result) + "\n")
    out.flush()
    for c in result["checks"]:
        err.write(f"check {c['name']} = {c['value']!r} "
                  f"(limit {c['limit']!r})\n")
    err.flush()
