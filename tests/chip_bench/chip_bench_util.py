"""Helpers for the chip benchmark's CPU tests: a temporary copy of the
benchmark with a throwaway cell at a size the CPU can hold."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"

for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CMP = {"source": "test", "total_ms": 20,
            "managers": ["baseline", "CBP"]}

TINY_MIXES = {"kind": "mixes", "mixes": {
    "w1": "xa,gr,li(2),h2,ze,to,so,lb,pe,ca,mi,sp,bw,go,ga",
    "w2": "lb,to,pe,go,gc,mi,li(2),na,h2,cac,ze(2),ca,so,as"}}

#: The sweep cell's own limits, so that the faults and the control are
#: judged by what the chip's runs are judged by.
TINY_SWEEP = dict(
    json.loads((BENCH / "workloads" / "sweep-table2.json").read_text()),
    trace_seconds=1)


def bench_copy(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like root in ``tmp`` holding ``BENCHMARK.json`` and a
    copy of the benchmark, with a throwaway cell ``tiny-sweep`` added as new
    files only."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    b = root / "benchmarks" / "chip"
    files = {
        b / "configs" / "tiny-cmp.json": TINY_CMP,
        b / "traffic" / "tiny-mixes.json": TINY_MIXES,
        b / "workloads" / "tiny-sweep.json": TINY_SWEEP,
    }
    for path, obj in files.items():
        assert not path.exists(), path
        path.write_text(json.dumps(obj))
    man["configs"].append(
        {"name": "tiny-cmp", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/chip/configs/tiny-cmp.json"})
    man["workloads"].append(
        {"name": "tiny-sweep", "config": "tiny-cmp", "traffic": "tiny-mixes",
         "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "sweep-table2" in m.get("workloads", ()):
            m["workloads"].append("tiny-sweep")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def run_tiny(root: pathlib.Path, cell: str, seed: int = 7,
             seconds: float = 0.5):
    """Drive the harness of the copy on the CPU, past its chip check."""
    import jax

    from bench import harness

    return harness.run_cell(root, cell, seed, seconds, False,
                            time.perf_counter(),
                            bench_dir=root / "benchmarks" / "chip",
                            devices=jax.devices()[:1])
