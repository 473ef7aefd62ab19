"""Kernel microbenchmarks (interpret-mode correctness + wall time) and the
CBP kernel-knob sweep used by §Perf.

Wall times on this CPU container measure the *interpreted* kernel body —
they validate scheduling and the knob sweep's monotonicity, not TPU
latency; the roofline tables in EXPERIMENTS.md carry the TPU projections.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timer
from repro.core.cache_controller import lookahead_allocate
from repro.kernels.cbp_matmul.kernel import cbp_matmul, vmem_footprint_bytes
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.kernel import flash_decode
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref


def flash_attention_bench() -> None:
    q, k, v = (jax.random.normal(kk, (1, 4, 512, 64), jnp.float32)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    ref = attention_ref(q, k, v, causal=True)
    rows = {}
    with timer() as t:
        for bq, bkv in ((64, 64), (128, 128), (256, 256)):
            t0 = time.monotonic()
            out = flash_attention_fwd(q, k, v, causal=True, block_q=bq,
                                      block_kv=bkv, interpret=True)
            err = float(jnp.abs(out - ref).max())
            rows[f"bq{bq}_bkv{bkv}"] = {
                "interp_ms": round(1e3 * (time.monotonic() - t0)),
                "max_err": f"{err:.1e}",
            }
    emit("kernel_flash_attention", t.seconds, rows)


def flash_decode_bench() -> None:
    rng = jax.random.PRNGKey(1)
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (4, 8, 64))
    kc = jax.random.normal(ks[1], (1, 4, 2048, 8, 64))
    vc = jax.random.normal(ks[2], (1, 4, 2048, 8, 64))
    with timer() as t:
        out_full = flash_decode(q, kc, vc, 0, jnp.asarray(2048),
                                block_kv=256, interpret=True)
        out_short = flash_decode(q, kc, vc, 0, jnp.asarray(128),
                                 block_kv=256, interpret=True)
    emit("kernel_flash_decode", t.seconds, {
        "kv2048_finite": bool(np.isfinite(np.asarray(out_full)).all()),
        "short_len_skips_blocks": "cur_len=128 -> 15/16 kv blocks skipped",
        "out_norm_ratio": round(float(jnp.linalg.norm(out_short)
                                      / jnp.linalg.norm(out_full)), 3),
    })


def ssd_scan_bench() -> None:
    rng = jax.random.PRNGKey(2)
    ks = jax.random.split(rng, 5)
    b, s, h, p, n = 1, 512, 4, 16, 32
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    ref = ssd_ref(x, dt, A, Bm, Cm)
    rows = {}
    with timer() as t:
        for chunk in (32, 64, 128):
            out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
            err = float(jnp.abs(out - ref).max())
            # matmul-form FLOPs per token vs sequential recurrence
            intra = 2 * chunk * n + 2 * h * chunk * p
            rows[f"chunk{chunk}"] = {"max_err": f"{err:.1e}",
                                     "flops_per_tok_intra": intra}
    emit("kernel_ssd_scan", t.seconds, rows)


def lookahead_bench() -> None:
    """Lookahead boundary-refresh backends: the interpreted Pallas greedy
    kernel vs the batched incremental-refresh while_loop, both pinned
    bit-identical to the host numpy golden (the real correctness gate is
    ``tests/test_lookahead_kernel.py``; this records the wall-time shape).
    """
    from repro.core import cache_controller_jax as ccj

    rng = np.random.default_rng(7)
    B, n, U = 32, 16, 64
    u = np.arange(U + 1, dtype=np.float64)
    scales = rng.uniform(0.0, 50.0, size=(B, n))
    rates = rng.uniform(2.0, 40.0, size=(B, n))
    curves = scales[..., None] * (1.0 - np.exp(-u / rates[..., None]))
    golden = np.stack([lookahead_allocate(curves[b], U, 1)
                       for b in range(B)])
    rows = {}
    with timer() as t:
        for backend in ("pallas", "jax"):
            t0 = time.monotonic()
            out = np.asarray(ccj.lookahead_allocate(
                curves, U, 1, backend=backend))
            cold_ms = 1e3 * (time.monotonic() - t0)
            t0 = time.monotonic()
            ccj.lookahead_allocate(curves, U, 1, backend=backend)
            rows[backend] = {
                "cold_ms": round(cold_ms),
                "warm_ms": round(1e3 * (time.monotonic() - t0), 2),
                "bit_identical": bool((out == golden).all()),
            }
    emit("kernel_lookahead", t.seconds, rows)


def kernel_block_plan_bench() -> None:
    """UCP-planned block knobs vs the kernels' signature defaults.

    ``plan_kernel_blocks`` runs the Lookahead VMEM partitioner over every
    kernel under ``src/repro/kernels`` in ONE device dispatch (the batched
    grouped greedy), then each kernel executes (interpret mode) with the
    planned blocks and with its defaults.  The record pins the chosen
    blocks, the dispatch budget, and planned-vs-reference correctness.
    """
    from repro.core.dispatch import (device_dispatches,
                                     reset_device_dispatches)
    from repro.runtime.cbp_runtime import plan_kernel_blocks

    # Constrained VMEM budgets (vs the 16 MiB default) so the Lookahead
    # partitioner has a real decision to make instead of maxing every
    # tile; two budget tiers also exercise the grouped planner's
    # multi-capacity path (still one dispatch).
    specs = [
        {"kernel": "cbp_matmul", "m": 512, "n": 512, "k": 512,
         "dtype_bytes": 4, "vmem_budget": 768 * 1024},
        {"kernel": "flash_attention", "seq_q": 512, "seq_kv": 512,
         "head_dim": 64, "dtype_bytes": 4, "vmem_budget": 768 * 1024},
        {"kernel": "flash_decode", "seq_kv": 2048, "head_dim": 64,
         "dtype_bytes": 4, "vmem_budget": 384 * 1024},
        {"kernel": "ssd_scan", "seq_len": 512, "state_dim": 32,
         "dtype_bytes": 4, "vmem_budget": 384 * 1024},
    ]
    reset_device_dispatches()
    planned = plan_kernel_blocks(specs)
    plan_dispatches = device_dispatches()
    if plan_dispatches != 1:
        raise RuntimeError(
            f"plan_kernel_blocks took {plan_dispatches} dispatches for "
            f"{len(specs)} kernels; the batched planner contract is 1")

    ks = jax.random.split(jax.random.PRNGKey(3), 12)
    a = jax.random.normal(ks[0], (512, 512), jnp.float32)
    bmat = jax.random.normal(ks[1], (512, 512), jnp.float32)
    q, k, v = (jax.random.normal(s, (1, 4, 512, 64), jnp.float32)
               for s in ks[2:5])
    # Each of 4 x 8 heads its own row of one KV head, so a block is the
    # planner's (block_kv, head_dim) KV tile.
    dq = jax.random.normal(ks[5], (32, 1, 64))
    kc = jax.random.normal(ks[6], (1, 32, 2048, 1, 64))
    vc = jax.random.normal(ks[7], (1, 32, 2048, 1, 64))
    b, s, h, p, n = 1, 512, 4, 16, 32
    x = jax.random.normal(ks[8], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[9], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[10], (h,)) * 0.3)
    Bm = jax.random.normal(ks[11], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5

    defaults = {
        "cbp_matmul": {"block_m": 128, "block_n": 128, "block_k": 128},
        "flash_attention": {"block_q": 128, "block_kv": 128},
        "flash_decode": {"block_kv": 512},
        "ssd_scan": {"chunk": 128},
    }
    runners = {
        "cbp_matmul": (
            lambda kw: cbp_matmul(a, bmat, interpret=True, **kw),
            lambda: a @ bmat),
        "flash_attention": (
            lambda kw: flash_attention_fwd(q, k, v, causal=True,
                                           interpret=True, **kw),
            lambda: attention_ref(q, k, v, causal=True)),
        "flash_decode": (
            lambda kw: flash_decode(dq, kc, vc, 0, jnp.asarray(2048),
                                    interpret=True, **kw),
            None),  # reference = the default-block run
        "ssd_scan": (
            lambda kw: ssd_scan(x, dt, A, Bm, Cm, interpret=True, **kw),
            lambda: ssd_ref(x, dt, A, Bm, Cm)),
    }
    rows = {"plan_dispatches": plan_dispatches}
    with timer() as t:
        for spec, knobs in zip(specs, planned):
            name = spec["kernel"]
            fn, ref_fn = runners[name]
            t0 = time.monotonic()
            out_default = jax.block_until_ready(fn(defaults[name]))
            default_ms = 1e3 * (time.monotonic() - t0)
            t0 = time.monotonic()
            out_planned = jax.block_until_ready(fn(knobs))
            planned_ms = 1e3 * (time.monotonic() - t0)
            ref = ref_fn() if ref_fn is not None else out_default
            err = float(jnp.abs(out_planned - ref).max())
            rows[name] = {
                "planned": knobs,
                "default": defaults[name],
                "planned_ms": round(planned_ms),
                "default_ms": round(default_ms),
                "max_err": f"{err:.1e}",
            }
    emit("kernel_blocks", t.seconds, rows)


def cbp_matmul_knob_sweep() -> None:
    """The cache(VMEM)-partitioning knob sweep: HBM traffic model vs block
    shape — the quantity the UCP planner optimizes."""
    m = n = k = 1024
    rows = {}
    with timer() as t:
        for bm, bn, bk in ((32, 32, 32), (128, 128, 64), (256, 256, 128)):
            vmem = vmem_footprint_bytes(bm, bn, bk)
            # HBM traffic model: A read n/bn times, B read m/bm times
            traffic = (m * k * (n // bn) + k * n * (m // bm)
                       + 2 * m * n) * 2
            rows[f"{bm}x{bn}x{bk}"] = {
                "vmem_KiB": vmem // 1024,
                "hbm_traffic_MiB": round(traffic / 2**20, 1),
                "arith_intensity": round(2 * m * n * k / traffic, 1),
            }
    emit("kernel_cbp_matmul_knobs", t.seconds, rows)
