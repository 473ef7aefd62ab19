"""Bring-up check: the evaluator and the serving engine on one TPU chip.

  python3 chip_smoke.py               # one chip: both phases
  python3 chip_smoke.py --four-chips  # four chips: the sharded paths only

Everything runs in this one process (a chip belongs to one process).

* Evaluator: ``run_sweep`` over the Table-2 mixes w1..w14 with every
  registered manager, two mixes checked against the numpy scalar path
  (``run_all_managers``), and the warm sweep held to <= 2 device programs.
* Serving: ``repro.launch.serve`` with the jitted engine on qwen3-8b at its
  published widths, depth cut to ``SERVE_LAYERS`` layers, random weights;
  every request must finish with finite logits, and the schedule must equal
  the host ``ServingEngine``'s on the same chip.
* ``--four-chips``: the same sweep sharded over the chips' (manager, mix)
  grid against the same golden, and ``JitServingEngine(n_groups=4)``
  against one single-group engine per group; each chip must have held data.

Each phase prints its compile seconds (JAX's own compile-duration events),
warm wall, device kind and peak device bytes.  The last line is one JSON
object; any failure raises and exits non-zero before it.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PARITY_MIXES = ("w1", "w14")
SWEEP_TOTAL_MS = 40.0
SERVE_LAYERS = 8
SERVE_ARGV = ["--arch", "qwen3-8b", "--full", "--layers", str(SERVE_LAYERS),
              "--engine", "jit", "--streams", "3", "--requests", "12",
              "--max-new", "16"]

_COMPILE_EVENTS = collections.Counter()


def _on_event(name, secs, **_kw):
    if name.startswith("/jax/core/compile/"):
        _COMPILE_EVENTS["s"] += secs


def _compile_secs() -> float:
    return float(_COMPILE_EVENTS["s"])


def check(ok: bool, what) -> None:
    """A gate that holds under ``python -O`` too (``assert`` would not)."""
    if not ok:
        raise AssertionError(what)


def _require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


def _peaks(devices):
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def _report(phase: str, compile_s: float, cold_s: float, warm_s: float,
            devices, **extra) -> None:
    row = {"phase": phase, "compile_s": compile_s, "cold_wall_s": cold_s,
           "warm_wall_s": warm_s, "device_kind": devices[0].device_kind,
           "peak_bytes_in_use": _peaks(devices), **extra}
    print(json.dumps(row), flush=True)


def _rel_err(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b) / (np.abs(a) + 1e-12)))


def evaluator_phase(devices, phase: str = "evaluator") -> None:
    """w1..w14 x all managers; numpy golden on PARITY_MIXES."""
    from repro.core.dispatch import device_dispatches, reset_device_dispatches
    from repro.distributed import grid_shard_counts
    from repro.sim import (
        MANAGER_NAMES,
        WORKLOADS,
        baseline_ipc,
        run_all_managers,
        run_sweep,
        weighted_speedup,
    )

    names = [f"w{i}" for i in range(1, 15)]
    mixes = [WORKLOADS[n] for n in names]
    c0, t0 = _compile_secs(), time.perf_counter()
    run_sweep(mixes, total_ms=SWEEP_TOTAL_MS)
    cold = time.perf_counter() - t0
    compile_s = _compile_secs() - c0
    reset_device_dispatches()
    t0 = time.perf_counter()
    res = run_sweep(mixes, total_ms=SWEEP_TOTAL_MS)
    warm = time.perf_counter() - t0
    dispatches = device_dispatches()
    check(dispatches <= 2, f"sweep took {dispatches} device programs")

    worst_base, worst_ws = 0.0, 0.0
    for mix_name in PARITY_MIXES:
        i = names.index(mix_name)
        base = baseline_ipc(mixes[i])
        err = _rel_err(res.baseline_ipc[i], base)
        check(err < 1e-5, (mix_name, "baseline", err))
        worst_base = max(worst_base, err)
        scalar = run_all_managers(mixes[i], total_ms=SWEEP_TOTAL_MS)
        for m in MANAGER_NAMES:
            ws_dev = float(res.weighted_speedup(m)[i])
            ws_ref = weighted_speedup(scalar[m].ipc, base)
            err = abs(ws_dev - ws_ref) / abs(ws_ref)
            check(err <= 1e-4, (mix_name, m, ws_dev, ws_ref))
            worst_ws = max(worst_ws, err)
    _report(phase, compile_s, cold, warm, devices,
            mixes=len(mixes), managers=len(MANAGER_NAMES),
            grid_shards=list(grid_shard_counts(len(MANAGER_NAMES),
                                               len(mixes))),
            dispatches=dispatches, parity_mixes=list(PARITY_MIXES),
            baseline_rel_err=worst_base, ws_rel_err=worst_ws,
            cbp_geomean_ws=float(res.geomean_speedup("CBP")))


def _schedule(eng, partition) -> dict:
    import numpy as np

    return {"steps": int(eng.steps), "reconfigs": int(eng.reconfigs),
            "partition": [int(p) for p in partition],
            "slot_share": np.asarray(eng.slot_share, np.float64),
            "queue_wait": np.asarray(eng.queue_wait, np.float64)}


def _same_schedule(a: dict, b: dict) -> None:
    import numpy as np

    for k in ("steps", "reconfigs", "partition"):
        check(a[k] == b[k], (k, a[k], b[k]))
    # The jitted engine carries shares in float32, the host in float64.
    np.testing.assert_allclose(a["slot_share"], b["slot_share"], rtol=1e-5)
    np.testing.assert_array_equal(a["queue_wait"], b["queue_wait"])


def _token_agreement(xs, ys) -> float:
    return sum(x.generated == y.generated for x, y in zip(xs, ys)) / len(xs)


def serving_phase(devices) -> None:
    """qwen3-8b at published widths, depth-cut; jit engine vs host."""
    from repro.launch import serve
    from repro.serving import ServingEngine

    c0, t0 = _compile_secs(), time.perf_counter()
    run = serve.serve(SERVE_ARGV)
    cold = time.perf_counter() - t0
    compile_s = _compile_secs() - c0
    eng = run.engine
    for r in run.requests:
        check(r.generated is not None, "request never admitted")
        check(len(r.generated) == r.max_new_tokens,
              (len(r.generated), r.max_new_tokens))
    check(eng.nonfinite_logits == 0, eng.nonfinite_logits)

    warm_reqs = serve.make_requests(run.args, run.cfg.vocab_size)
    t0 = time.perf_counter()
    eng.run(warm_reqs, max_steps=5000)
    warm = time.perf_counter() - t0
    check(eng.nonfinite_logits == 0, eng.nonfinite_logits)
    tokens = sum(len(r.generated) for r in warm_reqs)

    host = ServingEngine(run.model, run.params, n_streams=run.args.streams,
                         cfg=run.ecfg)
    host_reqs = serve.make_requests(run.args, run.cfg.vocab_size)
    host.run(host_reqs, max_steps=5000)
    jit_sched = _schedule(eng, eng.partition)
    host_sched = _schedule(host, host.pool.partition)
    _same_schedule(jit_sched, host_sched)
    _report("serving", compile_s, cold, warm, devices,
            arch=run.cfg.name, layers=run.cfg.n_layers,
            d_model=run.cfg.d_model, vocab=run.cfg.vocab_size,
            param_dtype=run.cfg.param_dtype,
            kv_cache_dtype=run.cfg.kv_cache_dtype,
            requests=len(warm_reqs), new_tokens=tokens,
            steps=jit_sched["steps"], reconfigs=jit_sched["reconfigs"],
            partition=jit_sched["partition"],
            slot_share=jit_sched["slot_share"].tolist(),
            schedule_matches_host=True,
            token_agreement_vs_host=_token_agreement(warm_reqs, host_reqs))


def four_chip_sweep(devices) -> None:
    """The sweep sharded over the (manager, mix) grid of four chips."""
    from repro.distributed import grid_shard_counts
    from repro.sim import MANAGER_NAMES

    shards = grid_shard_counts(len(MANAGER_NAMES), 14)
    check(shards[0] * shards[1] == 4, shards)
    evaluator_phase(devices, "evaluator_4chip")
    peaks = _peaks(devices)
    check(all(p > 0 for p in peaks), f"idle chip in the sweep: {peaks}")


def four_chip_serving(devices) -> None:
    """n_groups=4 sharded over the chips vs one single-group engine per
    group: the groups are independent, so each group's schedule must
    equal its solo engine's."""
    import numpy as np

    from repro.launch import serve
    from repro.models import build
    from repro.serving import EngineConfig, JitServingEngine, Request
    import jax

    groups, streams, slots, pages = 4, 8, 8, 128
    args = serve.parse_args(SERVE_ARGV + ["--streams", str(streams),
                                          "--requests", "24"])
    cfg = serve.model_config(args)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(batch_slots=slots, max_len=96, total_pages=pages,
                        page_tokens=8, reconfig_every_steps=24)

    c0, t0 = _compile_secs(), time.perf_counter()
    eng = JitServingEngine(model, params, n_streams=streams, cfg=ecfg,
                           n_groups=groups)
    reqs = serve.make_requests(args, cfg.vocab_size)
    eng.run(reqs, max_steps=5000)
    cold = time.perf_counter() - t0
    compile_s = _compile_secs() - c0
    K, M, a, b = eng._grid
    check(a * b == 4, eng._grid)
    check(eng.nonfinite_logits == 0, eng.nonfinite_logits)
    check(all(len(r.generated) == r.max_new_tokens for r in reqs),
          "a request fell short of its max_new_tokens")
    warm_reqs = serve.make_requests(args, cfg.vocab_size)
    t0 = time.perf_counter()
    eng.run(warm_reqs, max_steps=5000)
    warm = time.perf_counter() - t0
    peaks = _peaks(devices)
    check(all(p > 0 for p in peaks), f"idle chip in serving: {peaks}")

    npg = streams // groups
    solo_cfg = EngineConfig(batch_slots=slots // groups, max_len=96,
                            total_pages=pages // groups, page_tokens=8,
                            reconfig_every_steps=24)
    solo = JitServingEngine(model, params, n_streams=npg, cfg=solo_cfg)
    steps, reconfigs, agree = [], [], []
    part, share, wait = [], [], []
    for g in range(groups):
        mine = [r for r in serve.make_requests(args, cfg.vocab_size)
                if r.stream // npg == g]
        local = [Request(r.stream % npg, r.prompt, r.max_new_tokens)
                 for r in mine]
        solo.run(local, max_steps=5000)
        steps.append(solo.steps)
        reconfigs.append(solo.reconfigs)
        part += [int(p) for p in solo.partition]
        share += list(solo.slot_share)
        wait += list(solo.queue_wait)
        grouped = [r for r in warm_reqs if r.stream // npg == g]
        agree.append(_token_agreement(local, grouped))
    check(eng.steps == max(steps), (eng.steps, steps))
    check(eng.reconfigs == max(reconfigs), (eng.reconfigs, reconfigs))
    check([int(p) for p in eng.partition] == part, (eng.partition, part))
    np.testing.assert_array_equal(eng.slot_share, share)
    np.testing.assert_array_equal(eng.queue_wait, wait)
    _report("serving_4chip", compile_s, cold, warm, devices,
            arch=cfg.name, layers=cfg.n_layers, groups=groups,
            grid=list(eng._grid), streams=streams, slots=slots,
            steps=int(eng.steps), reconfigs=int(eng.reconfigs),
            schedule_matches_solo_groups=True,
            token_agreement_vs_solo=float(np.mean(agree)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded paths")
    args = ap.parse_args(argv)

    dev = _require_tpu()
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    devices = jax.devices()
    print(json.dumps({"jax": jax.__version__, "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "devices": [d.id for d in devices],
                      "compile_cache": cache}), flush=True)
    if args.four_chips:
        check(len(devices) == 4,
              f"--four-chips needs 4 chips, got {len(devices)}")
        four_chip_sweep(devices)
        four_chip_serving(devices)
    else:
        evaluator_phase(devices[:1])
        serving_phase(devices[:1])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
