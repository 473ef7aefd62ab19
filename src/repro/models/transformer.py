"""Decoder-only transformer (dense, MoE and VLM-backbone families).

Layers are stacked (leading ``L`` dim on every parameter) and executed with
``lax.scan`` so the lowered HLO stays compact — a 512-device SPMD compile of
a 60-layer model is one while loop, not 60 inlined layers (MaxText-style).
Rematerialization wraps the scanned body.

MoE uses gather-based dispatch (sort -> position-in-expert -> capacity
gather), batched expert matmul, and scatter-add combine.  Tokens are
replicated across the "model" axis (they are data-sharded only), experts
are sharded over "model": the gather is comm-free and the combine lowers to
one partial-sum all-reduce of the activation — the same per-layer collective
cost as a Megatron TP FFN, with FLOPs proportional to *active* experts only
(capacity_factor overhead aside).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models import layers as L
from repro.models.attention import (causal_attention, decode_attention,
                                    decode_attention_gqa, repeat_kv)
from repro.models.config import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ------------------------------------------------------------------ #
# Init
# ------------------------------------------------------------------ #


def init_attn(key, cfg: ModelConfig, n_layers: int) -> Dict:
    d, dh = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.padded_heads, cfg.n_kv_heads
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (n_layers, d, hq * dh), dt, in_axis=1),
        "wk": L.dense_init(ks[1], (n_layers, d, hkv * dh), dt, in_axis=1),
        "wv": L.dense_init(ks[2], (n_layers, d, hkv * dh), dt, in_axis=1),
        "wo": L.dense_init(ks[3], (n_layers, hq * dh, d), dt, in_axis=1),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n_layers, dh), dt)
        p["k_norm"] = jnp.ones((n_layers, dh), dt)
    return p


def init_mlp(key, cfg: ModelConfig, n_layers: int) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    return {
        "wg": L.dense_init(ks[0], (n_layers, d, f), dt, in_axis=1),
        "wu": L.dense_init(ks[1], (n_layers, d, f), dt, in_axis=1),
        "wd": L.dense_init(ks[2], (n_layers, f, d), dt, in_axis=1),
    }


def init_moe(key, cfg: ModelConfig, n_layers: int) -> Dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.padded_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": L.dense_init(ks[0], (n_layers, d, e), jnp.float32, in_axis=1),
        "wg": L.dense_init(ks[1], (n_layers, e, d, f), dt, in_axis=2),
        "wu": L.dense_init(ks[2], (n_layers, e, d, f), dt, in_axis=2),
        "wd": L.dense_init(ks[3], (n_layers, e, f, d), dt, in_axis=2),
    }


def init_params(key, cfg: ModelConfig) -> Dict:
    d, v = cfg.d_model, cfg.padded_vocab
    dt = _dtype(cfg)
    keys = jax.random.split(key, 6)
    layers = {
        "attn": init_attn(keys[0], cfg, cfg.n_layers),
        "ln1": jnp.ones((cfg.n_layers, d), dt),
        "ln2": jnp.ones((cfg.n_layers, d), dt),
    }
    if cfg.family == "moe":
        layers["moe"] = init_moe(keys[1], cfg, cfg.n_layers)
    else:
        layers["mlp"] = init_mlp(keys[1], cfg, cfg.n_layers)
    params = {
        "embed": L.embed_init(keys[2], (v, d), dt),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(keys[3], (d, v), dt, in_axis=0)
    return params


# ------------------------------------------------------------------ #
# Attention sublayer
# ------------------------------------------------------------------ #


def _project_qkv(p, cfg: ModelConfig, h):
    b, s, _ = h.shape
    dh = cfg.head_dim
    q = jnp.einsum("bsd,dk->bsk", h, p["wq"]).reshape(
        b, s, cfg.padded_heads, dh)
    k = jnp.einsum("bsd,dk->bsk", h, p["wk"]).reshape(
        b, s, cfg.n_kv_heads, dh)
    v = jnp.einsum("bsd,dk->bsk", h, p["wv"]).reshape(
        b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attention_block(p, cfg: ModelConfig, x, positions,
                    causal: bool = True,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill); ``scale`` defaults to
    head_dim ** -0.5."""
    b, s, d = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.rope_theta > 0:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    hq = "model" if cfg.heads_shardable else None
    q = constrain(q, "dp", None, hq, None)
    k = repeat_kv(k, cfg.n_rep)
    v = repeat_kv(v, cfg.n_rep)
    o = causal_attention(q, k, v, chunk=cfg.attn_chunk, causal=causal,
                         scale=scale)
    o = constrain(o, "dp", None, hq, None)
    return jnp.einsum("bsk,kd->bsd", o.reshape(b, s, -1), p["wo"])


def _decode_qkv(p, cfg: ModelConfig, x, cur_len):
    """The projection and RoPE of one-token decode: (q, k, v), each
    (B, 1, heads, Dh), rotated to ``cur_len`` (() or (B,))."""
    q, k, v = _project_qkv(p, cfg, x)   # (B, 1, H*, Dh)
    if cfg.rope_theta > 0:
        pos = jnp.reshape(cur_len, (-1,))[:, None]  # (B|1, 1)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
    return q, k, v


def attention_decode(p, cfg: ModelConfig, x, cache_k, cache_v, cur_len,
                     scale: Optional[float] = None):
    """One-token attention against the cache; returns (out, new_k, new_v).
    ``scale`` defaults to head_dim ** -0.5.

    cache_k/v: (B, Smax, Hkv, Dh), sequence-sharded over "model".
    ``cur_len`` is either a scalar () — every row writes/attends at the
    same position — or a per-row ``(B,)`` vector (continuous batching:
    each slot sits at its own position).  The vector form always takes
    the per-row scatter path: a per-row dynamic slice would unroll to B
    DUSes, while the scatter writes exactly B rows.
    """
    b = x.shape[0]
    q, k, v = _decode_qkv(p, cfg, x, cur_len)
    per_row = jnp.ndim(cur_len) >= 1
    if per_row:
        # Per-row scatter: touches B rows instead of masking the whole
        # (B, Smax) plane.  The vector form is only consumed by the
        # serving engines, whose caches are unsharded or BATCH-sharded
        # (slot axis) — on a sequence-sharded cache this scatter would
        # hit the same GSPMD all-gather as the DUS path below.
        write_at = jnp.asarray(cur_len, jnp.int32).reshape(-1)  # (B,)
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, write_at].set(
            _kv_store(cfg, k, cache_k)[:, 0])
        cache_v = cache_v.at[rows, write_at].set(
            _kv_store(cfg, v, cache_v)[:, 0])
    elif cfg.decode_cache_update == "onehot":
        write_at = jnp.asarray(cur_len, jnp.int32).reshape(())
        # Sharded-friendly ring-buffer write: a dynamic-index DUS on a
        # sequence-SHARDED dim makes GSPMD all-gather the whole cache;
        # the equivalent one-hot masked update is elementwise and stays
        # sharded (§Perf iteration C1).
        sel = (jnp.arange(cache_k.shape[1]) == write_at)[None, :, None,
                                                         None]
        cache_k = jnp.where(sel, _kv_store(cfg, k, cache_k), cache_k)
        cache_v = jnp.where(sel, _kv_store(cfg, v, cache_v), cache_v)
    else:
        write_at = jnp.asarray(cur_len, jnp.int32).reshape(())
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, _kv_store(cfg, k, cache_k), write_at, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, _kv_store(cfg, v, cache_v), write_at, axis=1)
    # Pin the ring-buffer layout (batch over DP when divisible, sequence
    # over model) so GSPMD never round-trips the cache through a reshard.
    if not cfg.pure_dp:
        from repro.distributed import get_dp_axes, get_mesh
        mesh = get_mesh()
        bax = None
        if mesh is not None:
            dp_n = 1
            for a in get_dp_axes():
                if a in mesh.axis_names:
                    dp_n *= mesh.shape[a]
            if cache_k.shape[0] % dp_n == 0 and cache_k.shape[0] >= dp_n:
                bax = "dp"
        cache_k = constrain(cache_k, bax, "model", None, None)
        cache_v = constrain(cache_v, bax, "model", None, None)
        # Split-KV decode: the cache stays sequence-sharded, so the tiny
        # (B, 1, H, Dh) query must be REPLICATED across "model" — letting
        # wq's head sharding propagate here makes GSPMD all-gather the
        # repeat_kv broadcast (2 GiB/layer for qwen3-8b; §Perf C2).
        q = constrain(q, bax, None, None, None)
    ckd = _kv_load(cfg, cache_k)
    cvd = _kv_load(cfg, cache_v)
    if cfg.decode_gqa == "grouped" and cfg.n_rep > 1:
        o = decode_attention_gqa(q, ckd, cvd, write_at + 1, scale)
    else:
        ck = repeat_kv(ckd, cfg.n_rep)
        cv = repeat_kv(cvd, cfg.n_rep)
        o = decode_attention(q, ck, cv, write_at + 1, scale)
    if not cfg.pure_dp:
        o = constrain(o, bax, None, None, None)
    out = jnp.einsum("bsk,kd->bsd", o.reshape(b, 1, -1), p["wo"])
    return out, cache_k, cache_v


# ------------------------------------------------------------------ #
# MoE FFN
# ------------------------------------------------------------------ #


def moe_ffn(p, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Gather-dispatch MoE (see module docstring).  x: (B, S, d).

    Tokens are processed in ``cfg.moe_groups`` groups (one per DP shard in
    production) so every gather/scatter is *batched over the group dim* —
    GSPMD keeps them shard-local instead of all-gathering tokens across DP.
    """
    b, s, d = x.shape
    e, k = cfg.padded_experts, cfg.top_k
    ng = cfg.moe_groups
    t = b * s
    assert t % ng == 0, (t, ng)
    tg = t // ng
    xg = constrain(x.reshape(ng, tg, d), "dp", None, None)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    if e > cfg.n_experts:  # padded experts are unroutable
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    gates, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Position-in-expert via per-group sort (no (T, E) one-hots).
    flat_e = topi.reshape(ng, tg * k)
    order = jnp.argsort(flat_e, axis=-1)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    run_start = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(e), side="left"))(
            sorted_e)                               # (G, E)
    pos_sorted = (jnp.arange(tg * k)[None, :]
                  - jnp.take_along_axis(run_start, sorted_e, axis=-1))
    pos = jax.vmap(
        lambda o, ps: jnp.zeros_like(ps).at[o].set(ps))(order, pos_sorted)

    cap = int(max(1, round(cfg.capacity_factor * tg * k / e)))
    keep = pos < cap
    sentinel = tg * k
    slot_ids = jnp.broadcast_to(
        jnp.arange(tg * k, dtype=jnp.int32)[None, :], (ng, tg * k))

    def scatter_idx(fe, po, kp, sl):
        buf = jnp.full((e, cap), sentinel, dtype=jnp.int32)
        return buf.at[(fe, jnp.minimum(po, cap - 1))].set(
            jnp.where(kp, sl, sentinel), mode="drop")

    idx = jax.vmap(scatter_idx)(flat_e, pos, keep, slot_ids)  # (G, E, C)
    valid = idx < sentinel
    tok = jnp.minimum(idx, sentinel - 1) // k       # token id per slot

    expert_in = jax.vmap(lambda xx, tt: xx[tt.reshape(-1)])(
        xg, tok).reshape(ng, e, cap, d)
    expert_in = jnp.where(valid[..., None], expert_in, 0.0)
    espec = "model" if cfg.moe_ep else None
    expert_in = constrain(expert_in, "dp", espec, None, None)

    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    if cfg.moe_gather_weights and not cfg.moe_ep:
        # FSDP experts: force the d-dim gather of the weights BEFORE the
        # einsum — one AG of weights per layer instead of partial-sum
        # all-reduces of the (much larger) activation intermediates
        # (§Perf iteration B4).
        wg = constrain(wg, espec, None, "model")
        wu = constrain(wu, espec, None, "model")
        wd = constrain(wd, espec, "model", None)
    gg = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, wg))
    uu = jnp.einsum("gecd,edf->gecf", expert_in, wu)
    y = jnp.einsum("gecf,efd->gecd", gg * uu, wd)  # (G, E, C, d)
    y = constrain(y, "dp", espec, None, None)

    # Combine: scatter-add weighted expert outputs back to token slots.
    w = jnp.where(
        valid, jnp.take_along_axis(
            gates.reshape(ng, tg * k),
            jnp.minimum(idx, sentinel - 1).reshape(ng, -1),
            axis=-1).reshape(ng, e, cap), 0.0)
    contrib = (y * w[..., None].astype(y.dtype)).reshape(ng, e * cap, d)
    target = jnp.where(valid, tok, tg).reshape(ng, e * cap)
    out = jax.vmap(
        lambda cc, tt: jnp.zeros((tg + 1, d), cc.dtype).at[tt].add(
            cc, mode="drop"))(contrib, target)
    out = constrain(out[:, :tg], "dp", None, None)
    return out.reshape(b, s, d)


# ------------------------------------------------------------------ #
# Layer + model forward
# ------------------------------------------------------------------ #


def _layer(p, cfg: ModelConfig, x, positions):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + attention_block(p["attn"], cfg, h, positions)
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        x = x + moe_ffn(p["moe"], cfg, h)
    else:
        x = x + L.swiglu(h, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"])
    seq = "model" if cfg.seq_shard_activations else None
    x = constrain(x, "dp", seq, None)
    return x


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def forward(params, cfg: ModelConfig, x_embed, positions) -> jnp.ndarray:
    """Run the layer stack on embedded inputs; returns final hidden."""
    seq = "model" if cfg.seq_shard_activations else None
    x = constrain(x_embed, "dp", seq, None)

    body = _maybe_remat(
        lambda x, lp: (_layer(lp, cfg, x, positions), None), cfg)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


def embed(params, cfg: ModelConfig, tokens) -> jnp.ndarray:
    return jnp.take(params["embed"], tokens, axis=0)


def logits_fn(params, cfg: ModelConfig, hidden) -> jnp.ndarray:
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    out = jnp.einsum("bsd,dv->bsv", hidden, head)
    return constrain(out, "dp", None, "model")


def loss_fn(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    if "embeddings" in batch:   # vlm/stub frontends feed embeddings
        x = batch["embeddings"].astype(_dtype(cfg))
    else:
        x = embed(params, cfg, batch["tokens"])
    positions = jnp.arange(x.shape[1])
    hidden = forward(params, cfg, x, positions)
    logits = logits_fn(params, cfg, hidden)
    return L.softmax_xent(logits, batch["labels"], cfg.vocab_size)


# ------------------------------------------------------------------ #
# Decode (serving)
# ------------------------------------------------------------------ #


KV_INT8_SCALE = 0.05   # fixed quantization step for int8 KV caches


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    dh = cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        dtype = jnp.int8
    return {
        "k": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, dh),
                       dtype),
        "v": jnp.zeros((cfg.n_layers, batch, max_len, cfg.n_kv_heads, dh),
                       dtype),
    }


def _kv_store(cfg: ModelConfig, x, like):
    """Quantize new K/V entries for an int8 cache."""
    if cfg.kv_cache_dtype == "int8":
        return jnp.clip(jnp.round(x.astype(jnp.float32) / KV_INT8_SCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(like.dtype)


def _kv_load(cfg: ModelConfig, cache):
    if cfg.kv_cache_dtype == "int8":
        return cache.astype(jnp.bfloat16) * KV_INT8_SCALE
    return cache


def _decode_ffn(lp, cfg: ModelConfig, x):
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        return x + moe_ffn(lp["moe"], cfg, h)
    return x + L.swiglu(h, lp["mlp"]["wg"], lp["mlp"]["wu"],
                        lp["mlp"]["wd"])


# Positions a block of the decode kernel reads: it divides the serving
# cell's 576 positions into three.
DECODE_BLOCK_KV = 192


def _kernel_reads_layer(cfg: ModelConfig, dtype) -> bool:
    """Whether the per-row decode reads each layer through
    ``kernels.flash_decode``.  The kernel takes a row's positions and KV
    heads as one (Smax * Hkv, Dh) plane.  A TPU tiles the cache's
    (Hkv, Dh) slabs by (Hkv, 128), ``4 // itemsize`` rows to a 32-bit
    word, and the plane by (8, 128): the two hold their bytes in the same
    order, so the plane is a bitcast, when Hkv is a whole number of words
    and divides 8 or is a multiple of it.  Compiled for a described v5e,
    bfloat16 caches with 2, 4, 8 or 16 KV heads and int8 ones with 4, 8 or
    16 bitcast; bfloat16 with 1, 6 or 12 and int8 with 2 copy the whole
    stacked cache in every layer, so those keep the XLA attention."""
    hkv, per_word = cfg.n_kv_heads, max(4 // jnp.dtype(dtype).itemsize, 1)
    return hkv % per_word == 0 and (hkv % 8 == 0 or 8 % hkv == 0)


def _decode_layers_rows(params, cfg: ModelConfig, cache, x, cur_len):
    """The layer scan of a decode step with every row at its own position
    (``cur_len`` of shape (B,)).

    The stacked caches (L, B, Smax, Hkv, Dh) ride in the scan's carry and
    the layer index is its input: each layer scatters its B new rows into
    the carry in place, then attends over its own layer of it.  Where
    ``_kernel_reads_layer`` holds, ``kernels.flash_decode`` reads the layer
    where it lies, and only up to each row's length; otherwise XLA's
    grouped attention copies the layer's slice out first (one pass over
    the whole cache a step).  As the scan's xs and ys, the caches would be
    sliced out a layer at a time and stacked into a fresh buffer that the
    serving engine copies back into its carried state: three passes over
    the whole cache a step to add B rows a layer.  The serving engines,
    the only callers of this form, hold their caches unsharded or sharded
    over the slot axis and set no ambient mesh, so nothing here is pinned
    to one; the attention is always the grouped one (``cfg.decode_gqa``
    acts on the scalar form alone).
    """
    b = x.shape[0]
    write_at = jnp.asarray(cur_len, jnp.int32).reshape(-1)     # (B,)
    rows = jnp.arange(b)
    if _kernel_reads_layer(cfg, cache["k"].dtype):
        # Imported here: Pallas takes about a second to import, which
        # models that never run the kernel (the hybrid's sites) need not
        # pay.
        from repro.kernels.flash_decode.ops import flash_decode

        kv_scale = KV_INT8_SCALE if cfg.kv_cache_dtype == "int8" else None

        def attend(q, ck, cv, i):
            return flash_decode(q[:, 0], ck, cv, i, write_at + 1,
                                block_kv=DECODE_BLOCK_KV, kv_scale=kv_scale)
    else:
        def attend(q, ck, cv, i):
            return decode_attention_gqa(q, _kv_load(cfg, ck[i]),
                                        _kv_load(cfg, cv[i]), write_at + 1)

    def body(carry, xs):
        x, ck, cv = carry
        lp, i = xs
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _decode_qkv(lp["attn"], cfg, h, write_at)
        ck = ck.at[i, rows, write_at].set(_kv_store(cfg, k, ck)[:, 0])
        cv = cv.at[i, rows, write_at].set(_kv_store(cfg, v, cv)[:, 0])
        o = attend(q, ck, cv, i)
        x = x + jnp.einsum("bsk,kd->bsd", o.reshape(b, 1, -1),
                           lp["attn"]["wo"])
        return (_decode_ffn(lp, cfg, x), ck, cv), None

    (x, ck, cv), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers)))
    return x, {"k": ck, "v": cv}


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_len):
    """One greedy decode step.  tokens: (B, 1) int32 (or embeddings
    (B, 1, d) for stub frontends); cur_len: () current cache length, or
    (B,) per row (``_decode_layers_rows``).  Returns (logits, new_cache)."""
    if tokens.ndim == 3:
        x = tokens.astype(_dtype(cfg))
    else:
        x = embed(params, cfg, tokens)

    if jnp.ndim(cur_len) >= 1:
        x, new_cache = _decode_layers_rows(params, cfg, cache, x, cur_len)
    else:
        def body(x, lp_and_cache):
            lp, ck, cv = lp_and_cache
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            att, nk, nv = attention_decode(lp["attn"], cfg, h, ck, cv,
                                           cur_len)
            return _decode_ffn(lp, cfg, x + att), (nk, nv)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": nk, "v": nv}
    hidden = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, hidden)
    return logits, new_cache
