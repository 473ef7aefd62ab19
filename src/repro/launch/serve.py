"""Serving launcher: CBP-managed batched decode for any --arch.

Without ``--full`` it runs the arch's reduced smoke config, which fits any
host.  ``--full`` binds the published widths; ``--layers N`` cuts the depth
so the weights fit one chip (qwen3-8b's 36 layers are 16.4 GB of bf16, a
v5e holds 16 GB; at 8 layers they are about 5.6 GB).  Weights are random
from a fixed seed.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b \\
      --requests 12 --streams 3 [--no-cbp] [--engine jit] \\
      [--full --layers 8]

``--engine jit`` swaps in the device-resident continuous-batching engine
(one jitted program per reconfiguration interval, in-trace CBP); with
``--groups G`` its stream groups shard across visible devices.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro import configs
from repro.models import build
from repro.models.config import ModelConfig
from repro.serving import (
    EngineConfig,
    JitServingEngine,
    Request,
    ServingEngine,
)


@dataclasses.dataclass
class ServeRun:
    """What one launch built and served, for callers that check it."""
    cfg: ModelConfig
    model: object
    params: dict
    ecfg: EngineConfig
    engine: object
    requests: List[Request]
    args: argparse.Namespace


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=configs.names())
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--streams", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--no-cbp", action="store_true")
    ap.add_argument("--engine", default="host", choices=("host", "jit"),
                    help="host = per-token Python loop; "
                         "jit = device-resident interval programs")
    ap.add_argument("--groups", type=int, default=1,
                    help="stream groups for --engine jit (sharded across "
                         "devices when more than one is visible)")
    ap.add_argument("--full", action="store_true",
                    help="published widths instead of the smoke config")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (widths unchanged)")
    return ap.parse_args(argv)


def model_config(args: argparse.Namespace) -> ModelConfig:
    cfg = (configs.get(args.arch) if args.full
           else configs.get_smoke(args.arch))
    if args.layers is not None:
        if not 1 <= args.layers <= cfg.n_layers:
            raise ValueError(f"--layers {args.layers} outside "
                             f"1..{cfg.n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    return cfg


def engine_config(args: argparse.Namespace) -> EngineConfig:
    return EngineConfig(
        batch_slots=args.slots, max_len=96, total_pages=16 * args.streams,
        page_tokens=8,
        reconfig_every_steps=(10 ** 9 if args.no_cbp else 24))


def make_requests(args: argparse.Namespace, vocab_size: int
                  ) -> List[Request]:
    """Stream 0 shares a hot 8-token prefix; the others draw 16-token
    prompts from the whole vocabulary."""
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        stream = i % args.streams
        if stream == 0:
            prompt = np.concatenate(
                [np.arange(8), rng.integers(8, 64, 4)])
        else:
            prompt = rng.integers(0, vocab_size - 1, 16)
        reqs.append(Request(stream=stream, prompt=prompt.astype(np.int32),
                            max_new_tokens=args.max_new))
    return reqs


def serve(argv: Optional[Sequence[str]] = None) -> ServeRun:
    """Build the model and engine, answer the requests, print a summary."""
    args = parse_args(argv)
    cfg = model_config(args)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ecfg = engine_config(args)
    if args.engine == "jit":
        engine = JitServingEngine(model, params, n_streams=args.streams,
                                  cfg=ecfg, n_groups=args.groups)
    else:
        engine = ServingEngine(model, params, n_streams=args.streams,
                               cfg=ecfg)
    reqs = make_requests(args, cfg.vocab_size)
    engine.run(reqs, max_steps=5000)

    print(f"arch={args.arch} {'full' if args.full else 'smoke'} "
          f"layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} engine={args.engine} "
          f"cbp={'off' if args.no_cbp else 'on'} "
          f"steps={engine.steps} reconfigs={engine.reconfigs}")
    if args.engine == "jit":
        partition, hit_rate = engine.partition, engine.demand_hit_rate
    else:
        partition = engine.pool.partition
        hit_rate = [engine.pool.stats[s].hit_rate
                    for s in range(args.streams)]
    for s in range(args.streams):
        print(f"  stream {s}: pages={int(partition[s]):3d} "
              f"hit-rate={hit_rate[s]:5.1%} "
              f"slots={engine.slot_share[s]:.2f}")
    done = sum(1 for r in reqs if r.generated)
    print(f"  completed {done}/{len(reqs)}")
    return ServeRun(cfg, model, params, ecfg, engine, reqs, args)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    serve()


if __name__ == "__main__":
    main()
