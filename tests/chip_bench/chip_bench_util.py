"""Helpers for the chip benchmark's CPU tests: a temporary copy of the
benchmark with throwaway cells at sizes the CPU can hold."""
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


#: A dense decoder at the qwen3 smoke widths (the repository's
#: ``configs.get_smoke("qwen3-8b")``), under the published names.
TINY_LM = {"source": "test", "hidden_size": 64, "intermediate_size": 128,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
           "rope_theta": 1e6, "vocab_size": 512, "qk_norm": True}


def _tiny_chat():
    """The chat mix with an eighth of its lengths, 6 conversations a
    tenant."""
    tr = json.loads((BENCH / "traffic" / "lmsys-chat-tenants.json")
                    .read_text())
    tr.update(conversations=6, prompt_mean=tr["prompt_mean"] / 8,
              response_mean=tr["response_mean"] / 8)
    return tr


def _tiny_serve():
    """The serving cell's own limits, at 8 slots and 96 steps a call, with
    8 requests a tenant in the logit check."""
    wl = json.loads((BENCH / "workloads" / "serve-chat-tenants.json")
                    .read_text())
    wl.update(max_steps=96, trace_seconds=1, sample_per_tenant=8)
    wl["engine"].update(batch_slots=8, max_len=96, total_pages=48,
                        reconfig_every_steps=8)
    return wl


def bench_copy(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like root in ``tmp`` holding ``BENCHMARK.json`` and a
    copy of the benchmark, with throwaway cells ``tiny-sweep`` and
    ``tiny-serve`` added as new files only."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads((REPO / "BENCHMARK.json").read_text())
    b = root / "benchmarks" / "chip"
    files = {
        b / "configs" / "tiny-cmp.json": TINY_CMP,
        b / "traffic" / "tiny-mixes.json": TINY_MIXES,
        b / "workloads" / "tiny-sweep.json": TINY_SWEEP,
        b / "configs" / "tiny-lm.json": TINY_LM,
        b / "traffic" / "tiny-chat.json": _tiny_chat(),
        b / "workloads" / "tiny-serve.json": _tiny_serve(),
    }
    for path, obj in files.items():
        assert not path.exists(), path
        path.write_text(json.dumps(obj))
    man["configs"].append(
        {"name": "tiny-cmp", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/chip/configs/tiny-cmp.json"})
    man["configs"].append(
        {"name": "tiny-lm", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/chip/configs/tiny-lm.json"})
    man["workloads"].append(
        {"name": "tiny-sweep", "config": "tiny-cmp", "traffic": "tiny-mixes",
         "chips": 1, "why": "test"})
    man["workloads"].append(
        {"name": "tiny-serve", "config": "tiny-lm", "traffic": "tiny-chat",
         "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        for real, tiny in (("sweep-table2", "tiny-sweep"),
                           ("serve-chat-tenants", "tiny-serve")):
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny)
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
