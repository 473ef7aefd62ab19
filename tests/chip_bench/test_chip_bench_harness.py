"""CPU tests of the chip benchmark's harness: it refuses to run without a
chip, finds a new cell by its files alone, and reads ``correct`` false
when the timed path is broken underneath."""
from __future__ import annotations

import io
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import chip_bench_util as u
from bench import harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return u.bench_copy(tmp_path_factory.mktemp("bench"))


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _run_py(cwd: pathlib.Path):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "sweep-table2", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)


def test_run_py_refuses_a_cpu_platform():
    r = _run_py(u.REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_run_py_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    man = json.loads((u.REPO / "BENCHMARK.json").read_text())
    shutil.copy(u.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(u.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_a_new_cell_needs_only_new_files(root):
    res = u.run_tiny(root, "tiny-sweep")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"sweep_mixes_per_s", "sweep_s_p90",
                                   "setup_s"}
    assert res["window"]["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"


def test_emit_puts_checks_last(capsys):
    res = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
           "device": {}, "checks": [{"name": "gap", "value": 0.5,
                                     "limit": 1.0}]}
    out, err = io.StringIO(), io.StringIO()
    harness.emit(res, out, err)
    assert json.loads(out.getvalue().splitlines()[-1])["checks"]
    assert err.getvalue().splitlines()[-1] == "check gap = 0.5 (limit 1.0)"


# ----------------------------------------------- faults under the timed path


def _sweep_patch(monkeypatch, change):
    import repro.sim.sweep as sweep_mod

    orig = sweep_mod.run_sweep

    def run_sweep(mixes, *a, **kw):
        return change(orig, list(mixes), a, kw)

    monkeypatch.setattr(sweep_mod, "run_sweep", run_sweep)
    import repro.sim as sim
    monkeypatch.setattr(sim, "run_sweep", run_sweep, raising=False)


def _alter_ipc(monkeypatch):
    """An answer altered where it is produced: one manager's IPC."""
    def change(orig, mixes, a, kw):
        res = orig(mixes, *a, **kw)
        res.ipc["CBP"] = np.asarray(res.ipc["CBP"]) * (1 + 1e-5)
        return res
    _sweep_patch(monkeypatch, change)


def _half_the_mixes(monkeypatch):
    """Half of the batch left out."""
    def change(orig, mixes, a, kw):
        return orig(mixes[: len(mixes) // 2], *a, **kw)
    _sweep_patch(monkeypatch, change)


def _unmanaged(monkeypatch):
    """A timeline that returns its state unchanged: every manager reports
    the unmanaged baseline's IPC."""
    def change(orig, mixes, a, kw):
        res = orig(mixes, *a, **kw)
        for m in res.ipc:
            res.ipc[m] = np.asarray(res.baseline_ipc)
        return res
    _sweep_patch(monkeypatch, change)


@pytest.mark.parametrize("fault", [_alter_ipc, _half_the_mixes, _unmanaged],
                         ids=lambda f: f.__name__)
def test_a_broken_timed_path_reads_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = u.run_tiny(root, "tiny-sweep", seed=9)
    assert res["correct"] is False, res["checks"]


def test_the_precision_control_in_the_programs_place_reads_not_correct(
        root, monkeypatch):
    """The reference's interval model in float32, put in the place of
    ``run_sweep`` and judged by the harness against the cell's limits."""
    entry = harness.load_module(
        root / "benchmarks" / "chip" / "entries" / "sweep.py",
        "bench_entry_sweep_control")

    def control(mixes, managers, total_ms):
        return entry.reference_sweep(mixes, managers, total_ms, np.float32)

    _sweep_patch(monkeypatch, lambda orig, mixes, a, kw: control(mixes, **kw))
    res = u.run_tiny(root, "tiny-sweep", seed=11)
    assert res["correct"] is False, res["checks"]
    base = {c["name"]: c for c in res["checks"]}["baseline_rel_err"]
    assert base["value"] > 10 * base["limit"]
