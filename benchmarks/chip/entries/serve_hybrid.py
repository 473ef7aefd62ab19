"""Entry: back-to-back ``JitServingEngine.run`` calls of a Zamba2 hybrid
over a fixed queue of multi-tenant chat requests, checked against the
plain references.

It drives the engine as ``entries/serve.py`` does (the same settings, the
same window, the same schedule), with the hybrid in place of the dense
model: the configuration file gives its sizes under their published names
(``reference.zamba2.Dims``), and the weights are drawn by
``reference.zamba2.make_weights``.

``correct`` compares, after the window, what ``entries/serve.py``
compares (the schedule against ``reference.serving``, exactly, and the
slot shares; served ids; non-finite logits; drained calls; the logit gap,
here against ``reference.zamba2`` in float32), and besides:

* the logit sample holds, per tenant, the longest finished request, the
  longest finished request admitted after step 0 (into a slot that
  served another request before: a request that would start from a
  stranger's state without the reset), and requests drawn from the seed.
  A tenant with no such request reads an infinite gap;
* the SSM state that the window's last call left in its slots: per
  tenant, the live request admitted after step 0 that has been fed the
  most tokens.  ``state_err`` is the largest relative error (the L2 norm
  over every layer) of such a request's state against the float32
  reference's state after the same tokens; ``state_bf16_share`` is the
  share of the sampled state's non-zero entries that bfloat16 holds
  exactly: about 2**-16 for a state kept in float32, as the
  configuration states it, and 1 for a state kept or computed in
  bfloat16.  The program's bfloat16 matrices move its state further
  from the float32 reference than a bfloat16 state does, so no limit on
  ``state_err`` could tell the two apart; the share does.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from bench import hybrid_counts, traffic
from entries import serve as dense
from reference import serving as ref_serving
from reference.zamba2 import Dims, logit_gaps, make_weights, ssm_states

REF_BATCH = 2     # sequences per reference call


def _program_layout(w: Dict, dims: Dims) -> Dict:
    """The benchmark's weights in the layout ``repro.models.hybrid``
    takes."""
    import jax.numpy as jnp

    di, c = dims.d_inner, dims.conv_dim
    m_in = w["m_in"]
    layers = {"ln": w["m_ln"], "wz": m_in[..., :di],
              "wxbc": m_in[..., di: di + c], "wdt": m_in[..., di + c:],
              "conv": w["m_conv_w"], "conv_b": w["m_conv_b"],
              "dt_bias": w["m_dt_bias"],
              "A_log": w["m_A_log"].astype(jnp.float32), "D": w["m_D"],
              "norm": w["m_norm"], "out": w["m_out"]}
    blocks = [{"ln_attn": w["b_ln_attn"][b],
               "attn": {k: w[f"b_{k}"][b] for k in ("wq", "wk", "wv", "wo")},
               "ln_mlp": w["b_ln_mlp"][b], "wgu": w["b_gate_up"][b],
               "wd": w["b_down"][b]} for b in range(dims.n_blocks)]
    sites = [{"ad_a": w["s_adapter_a"][s], "ad_b": w["s_adapter_b"][s],
              "lin": w["s_linear"][s]} for s in range(len(dims.sites))]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "layers": layers, "blocks": blocks, "sites": sites}


def _model_config(dims: Dims):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name="bench", family="hybrid", n_layers=dims.n_layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, d_head=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab_size, rope_theta=dims.rope_theta,
        norm_eps=dims.norm_eps, tie_embeddings=True,
        ssm_state=dims.ssm_state, ssm_conv=dims.conv_width,
        ssm_expand=dims.d_inner // dims.d_model,
        ssm_head_dim=dims.ssm_head_dim, ssm_ngroups=dims.ssm_groups,
        hybrid_layer_ids=dims.sites, num_mem_blocks=dims.n_blocks,
        adapter_rank=dims.adapter_rank, param_dtype="bfloat16",
        kv_cache_dtype="bfloat16", remat="none",
        seq_shard_activations=False)


def setup(ctx) -> dense.State:
    dims = Dims.from_config(ctx.config)
    mcfg = _model_config(dims)      # a program without the hybrid fails here
    from repro.models.model import Model
    from repro.serving import EngineConfig, JitServingEngine

    e = ctx.settings["engine"]
    longest = max(p + r for p, r in traffic.lengths(ctx.traffic))
    if longest > int(e["max_len"]):
        raise ValueError(f"a request of {longest} tokens does not fit the "
                         f"engine's max_len {e['max_len']}")
    ecfg = ref_serving.Engine(**e)
    params = make_weights(dims, ctx.seed, mcfg.padded_vocab,
                          layout=_program_layout)
    engine = JitServingEngine(Model(mcfg), params, ecfg.n_streams,
                              EngineConfig(**{k: v for k, v in e.items()
                                              if k not in ("n_streams",
                                                           "min_pages")}),
                              min_pages=ecfg.min_pages)
    state = dense.State(dims=dims, engine_cfg=ecfg,
                        queue=traffic.requests(ctx.traffic, ctx.seed,
                                               dims.vocab_size),
                        max_steps=int(ctx.settings["max_steps"]),
                        engine=engine)
    # the same shapes as every call of the window, one interval long
    engine.run(dense._fresh(state.queue), max_steps=ecfg.reconfig_every_steps)
    return state


window = dense.window
end_to_end = dense.end_to_end


def _lengths(state: dense.State):
    return [(r.stream, len(r.prompt), r.max_new_tokens) for r in state.queue]


def _admitted_later(state: dense.State) -> np.ndarray:
    """Per request, whether the reference schedule admits it after step
    0, into a slot that served another request before."""
    return ref_serving.run(_lengths(state), state.engine_cfg,
                           0).generated < 0


def _live_sample(state: dense.State, slot_request, slot_pos):
    """(slot, request, tokens fed) per tenant: of its live requests
    admitted after step 0 and fed at least one token, the one fed the
    most (the lowest slot among equals)."""
    later = _admitted_later(state)
    best = {}
    for slot, (i, pos) in enumerate(zip(slot_request, slot_pos)):
        if i < 0 or pos <= 0 or not later[i]:
            continue
        s = state.queue[i].stream
        if s not in best or pos > best[s][2]:
            best[s] = (slot, int(i), int(pos))
    return [best[s] for s in sorted(best)]


def _heads(ssm: np.ndarray, dims: Dims) -> np.ndarray:
    """The program's SSM state (L, n, G, N, Hg * P) as the reference's,
    (n, L, H, P, N)."""
    L, n, g, N, k = ssm.shape
    P = dims.ssm_head_dim
    return (ssm.reshape(L, n, g, N, k // P, P).transpose(1, 0, 2, 4, 5, 3)
            .reshape(n, L, g * k // P, P, N))


def release(state: dense.State) -> None:
    """Reads the SSM state of the sampled live requests
    (:func:`_live_sample`) that the window's last call left in the
    engine's slots into the record, then frees the weights, the engine
    and its cache before the reference runs."""
    eng = state.engine
    rows = _live_sample(state, eng.slot_request, eng.slot_pos)
    ssm = np.zeros((0,), np.float32)
    if rows:
        slots = np.array([slot for slot, _, _ in rows])
        ssm = _heads(np.asarray(eng.cache["state"][:, slots]), state.dims)
    state.last_record["live"] = {"requests": [i for _, i, _ in rows],
                                 "pos": [pos for _, _, pos in rows],
                                 "state": ssm}
    dense.release(state)


def _sample(state: dense.State, record: Dict, ctx) -> List[int]:
    """Indices into the queue: per tenant, its longest finished request of
    the last call, its longest finished request admitted after step 0,
    and ``sample_per_tenant - 2`` more drawn from the seed."""
    served = dense._served(record["calls"][-1])
    later = _admitted_later(state)
    k = int(ctx.settings["sample_per_tenant"])
    out = []
    for s in range(state.engine_cfg.n_streams):
        done = [i for i, r in enumerate(state.queue)
                if r.stream == s and served[i] == r.max_new_tokens]
        if not done:
            continue

        def longest(pool):
            return max(pool, key=lambda i: len(state.queue[i].prompt)
                       + served[i])

        picks = [longest(done)]
        reused = [i for i in done if later[i]]
        if reused and longest(reused) not in picks:
            picks.append(longest(reused))
        rest = [i for i in done if i not in picks]
        draw = traffic.rng(ctx.seed, 4, s).permutation(len(rest))
        picks += [rest[j] for j in sorted(draw[: max(k - len(picks), 0)])]
        out += picks
    return out


def _gaps(state: dense.State, record: Dict, ctx, weights,
          precision: str = "float32"):
    """Per sampled request: the reference's gap at each served position,
    or, in any other precision, the ids the reference puts first there."""
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
        gaps, top = logit_gaps(weights, state.dims, toks, want, precision)
        for j, i in enumerate(rows):
            n, lo = len(gen[i]), len(state.queue[i].prompt) - 1
            if precision != "float32":
                out[i] = top[j, lo: lo + n]
            else:
                g = gaps[j, lo: lo + n].astype(np.float64)
                g[want[j, lo: lo + n] >= V] = np.inf
                out[i] = g
    return out


def _ref_states(state: dense.State, record: Dict, weights,
                precision: str = "float32") -> np.ndarray:
    """The reference's SSM state (n, L, H, P, N) after the tokens each
    sampled live request was fed: its prompt, then what it was served."""
    live = record["live"]
    L = state.engine_cfg.max_len
    V = state.dims.vocab_size
    gen = record["calls"][-1]["generated"]
    out = []
    for b in range(0, len(live["requests"]), REF_BATCH):
        rows = live["requests"][b: b + REF_BATCH]
        upto = np.array(live["pos"][b: b + REF_BATCH])
        toks = np.zeros((len(rows), L), np.int64)
        for j, i in enumerate(rows):
            fed = np.concatenate([state.queue[i].prompt,
                                  np.asarray(gen[i] or [], np.int64)])
            toks[j, : upto[j]] = np.minimum(fed[: upto[j]], V - 1)
        out.append(ssm_states(weights, state.dims, toks, upto, precision
                              ).swapaxes(0, 1))
    return np.concatenate(out) if out else np.zeros((0,), np.float32)


def _state_err(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per sampled request, ||prog - ref|| / ||ref|| over every layer."""
    axes = tuple(range(1, ref.ndim))
    return np.sqrt(np.sum((prog.astype(np.float64) - ref) ** 2, axes)
                   / np.sum(np.asarray(ref, np.float64) ** 2, axes))


def _bf16_share(x: np.ndarray) -> float:
    """The share of the non-zero entries of ``x`` that bfloat16 holds
    exactly; 1 where there are none."""
    import ml_dtypes

    x = np.asarray(x, np.float32)
    x = x[x != 0]
    if not x.size:
        return 1.0
    return float(np.mean(x.astype(ml_dtypes.bfloat16).astype(np.float32)
                         == x))


def _resets_judged(state: dense.State, record: Dict, ctx) -> bool:
    """Whether every tenant's sample holds a request admitted after step
    0, so that the logit gap judges the reset of a reused slot."""
    later = _admitted_later(state)
    sampled = {state.queue[i].stream for i in _sample(state, record, ctx)
               if later[i]}
    return len(sampled) == state.engine_cfg.n_streams


def check(state: dense.State, record: Dict, ctx):
    lim = ctx.settings["limits"]
    sched = dense._schedule(state)
    one = hybrid_counts.work(state.dims, sched.steps, sched.position_steps)
    record["work"] = {k: v * len(record["calls"]) for k, v in one.items()}
    V = state.dims.vocab_size
    exact = share_err = invalid = nonfinite = drained = 0.0
    failed = attempted = 0
    for c in record["calls"]:
        served = dense._served(c)
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
    live = record["live"]
    errs = _state_err(live["state"], _ref_states(state, record, weights))
    del weights
    state_err = float(np.max(errs)) if len(errs) else float("inf")
    if len(errs) < state.engine_cfg.n_streams:
        print(f"serve check: {len(errs)} of {state.engine_cfg.n_streams} "
              f"tenants have a live request admitted after step 0 whose "
              f"state is judged", file=sys.stderr)
    worst = max((float(np.max(g)) for g in gaps.values() if len(g)),
                default=float("inf"))
    if not _resets_judged(state, record, ctx):
        print("serve check: a tenant's sample holds no request admitted "
              "after step 0", file=sys.stderr)
        worst = float("inf")
    failed += sum(1 for g in gaps.values() if np.max(g) > lim["logit_gap"])
    failed += int(np.sum(errs > lim["state_err"]))
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
        {"name": "state_err", "value": state_err,
         "limit": lim["state_err"]},
        {"name": "state_bf16_share", "value": _bf16_share(live["state"]),
         "limit": lim["state_bf16_share"]},
    ]
    return checks, attempted, failed


def control_in(state: dense.State, ctx, precision: str) -> Dict:
    """The last call of the window with each sampled request's served
    tokens judged as the ids that the reference in ``precision`` puts
    first, at every position of the same prompts and tokens, and the
    sampled live requests' SSM state as that reference leaves it; and
    every call's slot shares as the reference's Algorithm 1 gives them in
    bfloat16, the precision below the engine's float32."""
    import ml_dtypes

    record = state.last_record
    weights = make_weights(state.dims, ctx.seed, state.dims.vocab_size)
    judged = _gaps(state, record, ctx, weights, precision)
    live = dict(record["live"],
                state=_ref_states(state, record, weights, precision))
    del weights
    share = dense._schedule(state).slot_share.astype(ml_dtypes.bfloat16)
    calls = [dict(c, slot_share=share.astype(np.float64))
             for c in record["calls"]]
    return dict(record, calls=calls, judged=judged, live=live)


def control(state: dense.State, ctx) -> Dict:
    """The precision control: the reference with float8 matrices and
    K/V (:func:`control_in`)."""
    return control_in(state, ctx, "float8")
