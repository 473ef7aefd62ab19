"""Entry: back-to-back ``JitServingEngine.run`` calls over a fixed queue of
multi-tenant chat requests, checked against the plain references.

The configuration file gives the model's sizes under their published
names (``reference.dense_lm.Dims``); the cell's file gives the engine's
settings (``engine``), the decode steps of one call (``max_steps``), the
requests sampled per tenant for the logit check (``sample_per_tenant``)
and the ``limits`` of the comparison.  Every call of a run serves the same
request list, so every call has the same schedule and the same shapes.
A call starts with every slot empty and lasts ``max_steps`` steps, more
than the longest request takes, so that requests of every length the
traffic sends finish in it; the window runs calls back to back until its
seconds have passed, and so may hold a single call.

``correct`` compares, after the window:

* the schedule of every call with ``reference.serving`` driven by the
  lengths alone: steps, reconfigurations, page partition, per-stream
  slot-steps, queue wait, demand hit rate and the tokens each request
  was served, exactly; the slot shares within ``slot_share_err``;
* every served id against the vocabulary, and the engine's count of
  logits that were not finite;
* the logits: a sample of the last call's finished requests, drawn from
  the seed with each tenant's longest among them, teacher-forced through
  ``reference.dense_lm`` in float32; at every served position the
  reference's best logit less its logit for the served token.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from bench import model_counts, traffic
from reference import serving as ref_serving
from reference.dense_lm import Dims, logit_gaps, make_weights

REF_BATCH = 2     # sequences per reference call


@dataclasses.dataclass
class State:
    dims: Dims
    engine_cfg: ref_serving.Engine
    queue: List[traffic.Request]
    max_steps: int
    engine: Optional[object] = None
    last_record: Optional[Dict] = None


def _program_layout(w: Dict) -> Dict:
    """The benchmark's weights in the layout ``repro.models.transformer``
    takes (head as ``(d, vocab)``)."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
            if k in w}
    return {"embed": w["embed"], "head": w["head"].T,
            "final_norm": w["final_norm"],
            "layers": {"attn": attn, "ln1": w["ln1"], "ln2": w["ln2"],
                       "mlp": {k: w[k] for k in ("wg", "wu", "wd")}}}


def _model_config(dims: Dims):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name="bench", family="dense", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_head=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab_size, qk_norm=dims.qk_norm,
        rope_theta=dims.rope_theta, norm_eps=dims.norm_eps,
        tie_embeddings=False, param_dtype="bfloat16",
        kv_cache_dtype="bfloat16", remat="none",
        seq_shard_activations=False)


def _fresh(queue: List[traffic.Request]):
    from repro.serving import Request

    return [Request(stream=r.stream, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in queue]


def setup(ctx) -> State:
    from repro.models.model import Model
    from repro.serving import EngineConfig, JitServingEngine

    dims = Dims.from_config(ctx.config)
    e = ctx.settings["engine"]
    longest = max(p + r for p, r in traffic.lengths(ctx.traffic))
    if longest > int(e["max_len"]):
        raise ValueError(f"a request of {longest} tokens does not fit the "
                         f"engine's max_len {e['max_len']}")
    ecfg = ref_serving.Engine(**e)
    mcfg = _model_config(dims)
    params = make_weights(dims, ctx.seed, mcfg.padded_vocab,
                          layout=_program_layout)
    engine = JitServingEngine(Model(mcfg), params, ecfg.n_streams,
                              EngineConfig(**{k: v for k, v in e.items()
                                              if k not in ("n_streams",
                                                           "min_pages")}),
                              min_pages=ecfg.min_pages)
    state = State(dims=dims, engine_cfg=ecfg,
                  queue=traffic.requests(ctx.traffic, ctx.seed,
                                         dims.vocab_size),
                  max_steps=int(ctx.settings["max_steps"]), engine=engine)
    # the same shapes as every call of the window, one interval long
    engine.run(_fresh(state.queue), max_steps=ecfg.reconfig_every_steps)
    return state


_ATTRS = ("steps", "reconfigs", "intervals", "nonfinite_logits")
_ARRAYS = ("partition", "slot_share", "queue_wait", "tokens_done",
           "demand_hit_rate")


def window(state: State, seconds: float, ctx) -> Dict:
    import jax

    eng = state.engine
    calls = []
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        reqs = _fresh(state.queue)
        with jax.profiler.TraceAnnotation("bench.call"):
            eng.run(reqs, max_steps=state.max_steps)
        end = time.perf_counter()
        calls.append(dict({a: int(getattr(eng, a)) for a in _ATTRS},
                          **{a: np.array(getattr(eng, a)) for a in _ARRAYS},
                          generated=[r.generated for r in reqs]))
        if end >= deadline:
            break
    record = {"kind": "serve", "window_s": end - start, "calls": calls,
              "slots": state.engine_cfg.batch_slots}
    state.last_record = record
    return record


def _served(call) -> np.ndarray:
    """Tokens served to each request of a call; -1: never admitted."""
    return np.array([-1 if g is None else len(g) for g in call["generated"]])


def _schedule(state: State) -> ref_serving.Schedule:
    return ref_serving.run(
        [(r.stream, len(r.prompt), r.max_new_tokens) for r in state.queue],
        state.engine_cfg, state.max_steps)


def _work(state: State, record: Dict, sched: ref_serving.Schedule) -> Dict:
    one = model_counts.work(state.dims, sched.steps, sched.position_steps)
    n = len(record["calls"])
    return {k: v * n for k, v in one.items()}


def end_to_end(record: Dict, ctx) -> Dict[str, float]:
    tokens = sum(int(np.maximum(_served(c), 0).sum())
                 for c in record["calls"])
    return {"serve_tokens_per_s": tokens / record["window_s"]}


def release(state: State) -> None:
    """Frees the weights and the compiled engine before the reference
    runs; the window's KV cache was freed with its last call."""
    state.engine = None


def _sample(state: State, record: Dict, ctx) -> List[int]:
    """Indices into the queue: per tenant, its longest finished request of
    the last call and ``sample_per_tenant - 1`` more drawn from the seed."""
    served = _served(record["calls"][-1])
    k = int(ctx.settings["sample_per_tenant"])
    out = []
    for s in range(state.engine_cfg.n_streams):
        done = [i for i, r in enumerate(state.queue)
                if r.stream == s and served[i] == r.max_new_tokens]
        if not done:
            continue
        longest = max(done, key=lambda i: len(state.queue[i].prompt)
                      + served[i])
        rest = [i for i in done if i != longest]
        pick = traffic.rng(ctx.seed, 4, s).permutation(len(rest))[: k - 1]
        out += [longest] + [rest[j] for j in sorted(pick)]
    return out


def _gaps(state: State, record: Dict, ctx, weights, low_precision=False):
    """Per sampled request: the reference's gap at each served position,
    or, with ``low_precision``, the ids the control puts first there."""
    L = state.engine_cfg.max_len
    V = state.dims.vocab_size
    gen = record["calls"][-1]["generated"]
    judged = record.get("judged", {})
    idx = _sample(state, record, ctx)
    out = {}
    for b in range(0, len(idx), REF_BATCH):
        rows = idx[b: b + REF_BATCH]
        toks = np.zeros((len(rows), L), np.int64)
        want = np.zeros((len(rows), L), np.int64)
        for j, i in enumerate(rows):
            p, g = state.queue[i].prompt, np.asarray(gen[i], np.int64)
            ctxt = np.concatenate([p, g[:-1]])
            toks[j, : len(ctxt)] = np.minimum(ctxt, V - 1)
            want[j, len(p) - 1: len(p) - 1 + len(g)] = judged.get(i, g)
        gaps, top = logit_gaps(weights, state.dims, toks, want,
                               low_precision)
        for j, i in enumerate(rows):
            n, lo = len(gen[i]), len(state.queue[i].prompt) - 1
            if low_precision:
                out[i] = top[j, lo: lo + n]
            else:
                g = gaps[j, lo: lo + n].astype(np.float64)
                g[want[j, lo: lo + n] >= V] = np.inf
                out[i] = g
    return out


def check(state: State, record: Dict, ctx):
    lim = ctx.settings["limits"]
    sched = _schedule(state)
    record["work"] = _work(state, record, sched)
    V = state.dims.vocab_size
    exact = share_err = invalid = nonfinite = drained = 0.0
    failed = attempted = 0
    for c in record["calls"]:
        served = _served(c)
        wrong = served != sched.generated
        ids = np.concatenate([np.asarray(g, np.int64)
                              for g in c["generated"] if g] or [[]])
        mismatched = [
            name for name, same in (
                ("steps", c["steps"] == sched.steps),
                ("reconfigs", c["reconfigs"] == sched.reconfigs),
                ("partition", np.array_equal(c["partition"],
                                             sched.partition)),
                ("tokens_done", np.array_equal(c["tokens_done"],
                                               sched.tokens_done)),
                ("queue_wait", np.array_equal(c["queue_wait"],
                                              sched.queue_wait)),
                ("demand_hit_rate", np.array_equal(c["demand_hit_rate"],
                                                   sched.demand_hit_rate)),
                ("served", not wrong.any()))
            if not same]
        if mismatched:
            print(f"serve check: call schedule differs from the reference "
                  f"in {mismatched}", file=sys.stderr)
        exact += len(mismatched)
        share_err = max(share_err, float(np.max(np.abs(
            c["slot_share"] - sched.slot_share))))
        invalid += int(np.sum(ids >= V) + np.sum(ids < 0))
        nonfinite += c["nonfinite_logits"]
        drained += served.min() >= 0            # no request left pending
        attempted += int(np.sum(served >= 0))
        failed += int(np.sum(wrong)) + sum(
            1 for g in c["generated"] if g and max(g) >= V)

    weights = make_weights(state.dims, ctx.seed, V)
    gaps = _gaps(state, record, ctx, weights)
    del weights
    worst = max((float(np.max(g)) for g in gaps.values() if len(g)),
                default=float("inf"))
    failed += sum(1 for g in gaps.values() if np.max(g) > lim["logit_gap"])
    checks = [
        {"name": "schedule_mismatches", "value": exact,
         "limit": lim["schedule_mismatches"]},
        {"name": "slot_share_err", "value": share_err,
         "limit": lim["slot_share_err"]},
        {"name": "invalid_ids", "value": invalid,
         "limit": lim["invalid_ids"]},
        {"name": "nonfinite_logits", "value": nonfinite,
         "limit": lim["nonfinite_logits"]},
        {"name": "drained_calls", "value": drained,
         "limit": lim["drained_calls"]},
        {"name": "logit_gap", "value": worst, "limit": lim["logit_gap"]},
    ]
    return checks, attempted, failed


def control(state: State, ctx) -> Dict:
    """The precision control in the program's place: the last call of the
    window with each sampled request's served tokens judged as the ids
    that the reference with float8 matrices and K/V puts first, at every
    position of the same prompts and tokens; and every call's slot shares
    as the reference's Algorithm 1 gives them in bfloat16, the precision
    below the engine's float32."""
    import ml_dtypes

    record = state.last_record
    weights = make_weights(state.dims, ctx.seed, state.dims.vocab_size)
    judged = _gaps(state, record, ctx, weights, low_precision=True)
    del weights
    share = _schedule(state).slot_share.astype(ml_dtypes.bfloat16)
    calls = [dict(c, slot_share=share.astype(np.float64))
             for c in record["calls"]]
    return dict(record, calls=calls, judged=judged)
