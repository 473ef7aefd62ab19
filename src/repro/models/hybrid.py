"""Zamba2 hybrid (arXiv:2411.15242; the block as transformers'
``modeling_zamba2`` computes it): a Mamba2 backbone with
``num_mem_blocks`` shared attention+MLP blocks, used in turn at the layers
``hybrid_layer_ids``.

With e the token's embedding and h the residual (h = e at the start),
layer i computes  h <- h + Mamba2_i(RMSNorm(h + t)),  where t = 0 except
at a site: the s-th entry of ``hybrid_layer_ids``, which uses shared block
b = s mod ``num_mem_blocks`` and weights of its own (A_s, B_s, W_lin,s):

  u = RMSNorm_2d([h, e]);  a = Attn_b(u), heads of ``head_dim`` read from
  all 2 d channels, RoPE over all of each head, softmax scale
  (head_dim / 2) ** -0.5, output projected to d;
  m = RMSNorm_d(a);  [g | up] = m W_gu,b + (m A_s) B_s;
  t = ((GELU(g) * up) W_down,b) W_lin,s   (exact erf GELU).

The residual does not take t: t only enters the Mamba layer's input.
The head is the tied embedding.  The layers between two sites run as one
loop; the sites are unrolled, so each finds its block and its own weights
statically.  The decode carries each layer's conv window and SSM state and
each site's K/V and updates them in place; a row at
position 0 starts from a zero recurrent state (``ssm.start_fresh``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.config import ModelConfig


def init_params(key, cfg: ModelConfig) -> Dict:
    d, v, f, r = cfg.d_model, cfg.padded_vocab, cfg.d_ff, cfg.adapter_rank
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    k_embed, k_mamba, k_blocks, k_sites = jax.random.split(key, 4)

    def block(key):
        ks = jax.random.split(key, 6)
        return {
            "ln_attn": jnp.ones((2 * d,), dt),
            "attn": {
                "wq": L.dense_init(ks[0], (2 * d, hq), dt),
                "wk": L.dense_init(ks[1], (2 * d, hkv), dt),
                "wv": L.dense_init(ks[2], (2 * d, hkv), dt),
                "wo": L.dense_init(ks[3], (hq, d), dt),
            },
            "ln_mlp": jnp.ones((d,), dt),
            "wgu": L.dense_init(ks[4], (d, 2 * f), dt),
            "wd": L.dense_init(ks[5], (f, d), dt),
        }

    def site(key):
        ks = jax.random.split(key, 3)
        return {"ad_a": L.dense_init(ks[0], (d, r), dt),
                "ad_b": L.dense_init(ks[1], (r, 2 * f), dt),
                "lin": L.dense_init(ks[2], (d, d), dt)}

    params = {
        "embed": L.embed_init(k_embed, (v, d), dt),
        "layers": S.init_mamba(k_mamba, cfg, cfg.n_layers),
        "blocks": [block(k) for k in
                   jax.random.split(k_blocks, cfg.num_mem_blocks)],
        "sites": [site(k) for k in
                  jax.random.split(k_sites, len(cfg.hybrid_layer_ids))],
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.dense_init(jax.random.fold_in(key, 1), (d, v),
                                      dt)
    return params


def attn_scale(cfg: ModelConfig) -> float:
    return (cfg.head_dim / 2) ** -0.5


def _block(params, s: int) -> Dict:
    """The shared block that site ``s`` uses."""
    blocks = params["blocks"]
    return blocks[s % len(blocks)]


def _adapter(site, m):
    """Site's rank-r addition to the shared block's gate and up
    projections."""
    return (m @ site["ad_a"]) @ site["ad_b"]


def _site_out(block, site, cfg: ModelConfig, a):
    """t of a site from its attention output ``a`` (..., d)."""
    m = L.rms_norm(a, block["ln_mlp"], cfg.norm_eps)
    g, up = jnp.split(m @ block["wgu"] + _adapter(site, m), 2, axis=-1)
    return ((jax.nn.gelu(g, approximate=False) * up) @ block["wd"]
            ) @ site["lin"]


def _attn_input(block, cfg: ModelConfig, h, e):
    return L.rms_norm(jnp.concatenate([h, e], axis=-1), block["ln_attn"],
                      cfg.norm_eps)


def _layer(params, i: int):
    return jax.tree.map(lambda a: a[i], params["layers"])


def _mamba_stack(params, cfg: ModelConfig, x, lo: int, hi: int):
    """Mamba layers ``lo .. hi - 1`` over a sequence."""
    if hi <= lo:
        return x

    def body(x, lp):
        x = S.mamba_block(lp, cfg, x)
        seq = "model" if cfg.seq_shard_activations else None
        return constrain(x, "dp", seq, None), None

    x, _ = jax.lax.scan(T._maybe_remat(body, cfg), x,
                        jax.tree.map(lambda a: a[lo:hi], params["layers"]))
    return x


def forward(params, cfg: ModelConfig, x, positions) -> jnp.ndarray:
    """Final hidden (after the last norm) of embedded inputs ``x``."""
    e, lo = x, 0
    for s, i in enumerate(cfg.hybrid_layer_ids):
        x = _mamba_stack(params, cfg, x, lo, i)
        blk = _block(params, s)
        a = T.attention_block(blk["attn"], cfg, _attn_input(blk, cfg, x, e),
                              positions, scale=attn_scale(cfg))
        t = _site_out(blk, params["sites"][s], cfg, a)
        x = S.mamba_block(_layer(params, i), cfg, x, t)
        lo = i + 1
    x = _mamba_stack(params, cfg, x, lo, cfg.n_layers)
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


def loss_fn(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    x = T.embed(params, cfg, batch["tokens"])
    positions = jnp.arange(x.shape[1])
    hidden = forward(params, cfg, x, positions)
    logits = T.logits_fn(params, cfg, hidden)
    return L.softmax_xent(logits, batch["labels"], cfg.vocab_size)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """Recurrent state of every layer (float32), and K and V of each site
    as a buffer of its own in ``transformer.init_cache``'s layout, (1,
    slot, position, head, head_dim): a site's write aliases nothing but its
    own buffer, and the leading axis of one keeps the slot axis at
    position 1, where the serving engine looks for it on every cache
    leaf."""
    cache = S.init_ssm_cache(cfg, batch, cfg.n_layers)
    shape = (1, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        dtype = jnp.int8
    n = len(cfg.hybrid_layer_ids)
    cache["k"] = [jnp.zeros(shape, dtype) for _ in range(n)]
    cache["v"] = [jnp.zeros(shape, dtype) for _ in range(n)]
    return cache


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_len):
    """One greedy decode step; every row at its own position."""
    x = T.embed(params, cfg, tokens)
    pos = S.row_positions(cur_len, x.shape[0])
    fresh = pos == 0
    e, lo = x, 0
    conv, state = cache["conv"], cache["state"]
    ck, cv = list(cache["k"]), list(cache["v"])
    for s, i in enumerate(cfg.hybrid_layer_ids):
        with jax.named_scope("cbp.serve.mamba"):
            x, conv, state = S.decode_layers(params["layers"], cfg, x, conv,
                                             state, lo, i, fresh)
        with jax.named_scope("cbp.serve.shared_block"):
            blk = _block(params, s)
            a, k, v = T.attention_decode(
                blk["attn"], cfg, _attn_input(blk, cfg, x, e), ck[s][0],
                cv[s][0], pos, scale=attn_scale(cfg))
            ck[s], cv[s] = k[None], v[None]
            t = _site_out(blk, params["sites"][s], cfg, a)
        with jax.named_scope("cbp.serve.mamba"):
            x, conv, state = S.decode_layers(params["layers"], cfg, x, conv,
                                             state, i, i + 1, fresh, t)
        lo = i + 1
    with jax.named_scope("cbp.serve.mamba"):
        x, conv, state = S.decode_layers(params["layers"], cfg, x, conv,
                                         state, lo, cfg.n_layers, fresh)
    hidden = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = T.logits_fn(params, cfg, hidden)
    return logits, {"conv": conv, "state": state, "k": ck, "v": cv}
