"""Mamba2 (state-space duality / SSD) blocks — arXiv:2405.21060.

The SSD recurrence  h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) ,
y_t = C_t . h_t + D x_t  is evaluated with the chunked matmul-form
algorithm (intra-chunk attention-like block + inter-chunk state
recurrence), which is what makes it MXU-friendly on TPU.  ``lax.scan``
runs over chunks (sequential inter-chunk state) and the per-chunk math is
batched matmuls; on real TPU hardware the per-chunk body is the Pallas
kernel in ``repro.kernels.ssd_scan`` and this jnp path is its oracle.

The block is the published Mamba2 mixer: one input projection to z, xBC
and dt; a causal depthwise conv with a bias over xBC (x and the B/C of
``ssm_ngroups`` groups, heads split evenly among the groups), then SiLU;
the SSD recurrence; an RMSNorm of ``y * SiLU(z)`` over each group's
channels; the output projection.

Sharding: SSD heads are sharded over the "model" axis (64 heads for
mamba2-1.3b, 112 for zamba2-7b — both divisible by 16), and so are the
conv's channels.  The input projection's xBC columns are sharded as one
block (``wxbc``), so the cut between x and B/C falls inside a shard: on a
mesh, besides the in/out projections' boundary collectives, every layer
reshards x and gathers B and C.  Splitting ``wxbc`` into an x and a B/C
leaf would remove that; no cell runs the block on more than one chip.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models import layers as L
from repro.models.config import ModelConfig

F32 = jnp.float32


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def init_mamba(key, cfg: ModelConfig, n_layers: int) -> Dict:
    d, di, c = cfg.d_model, cfg.d_inner, cfg.conv_dim
    h, k = cfg.ssm_heads, cfg.ssm_conv
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.ones((n_layers, d), dt),
        "wz": L.dense_init(ks[0], (n_layers, d, di), dt, in_axis=1),
        "wxbc": L.dense_init(ks[1], (n_layers, d, c), dt, in_axis=1),
        "wdt": L.dense_init(ks[2], (n_layers, d, h), dt, in_axis=1),
        "conv": (jax.random.normal(ks[3], (n_layers, c, k), F32)
                 * (1.0 / k)).astype(dt),
        "conv_b": jnp.zeros((n_layers, c), dt),
        "dt_bias": jnp.zeros((n_layers, h), dt),
        "A_log": jnp.zeros((n_layers, h), F32),
        "D": jnp.ones((n_layers, h), dt),
        "norm": jnp.ones((n_layers, di), dt),
        "out": L.dense_init(ks[4], (n_layers, di, d), dt, in_axis=1),
    }


def causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv.  x: (B, S, C); w: (C, K)."""
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # (B, S+K-1, C) -> windows via conv_general_dilated, depthwise.
    out = jax.lax.conv_general_dilated(
        xp.transpose(0, 2, 1)[:, :, None, :],            # (B, C, 1, S+K-1)
        w[:, None, None, :].astype(x.dtype),             # (C, 1, 1, K)
        window_strides=(1, 1), padding="VALID",
        feature_group_count=w.shape[0],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return out[:, :, 0, :].transpose(0, 2, 1)            # (B, S, C)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int,
                initial_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan (pure-jnp oracle).

    x: (B, S, H, P); dt: (B, S, H); A: (H,) negative; Bm/Cm: (B, S, G, N)
    for G groups (heads split evenly, in order, among them) or (B, S, N)
    for one.  Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    if Bm.ndim == 3:
        Bm, Cm = Bm[:, :, None], Cm[:, :, None]
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    hg = h // g
    cl = min(chunk, s)
    assert s % cl == 0, (s, cl)
    nc = s // cl

    xr = x.reshape(b, nc, cl, g, hg, p).astype(F32)
    dtr = dt.reshape(b, nc, cl, g, hg).astype(F32)
    Br = Bm.reshape(b, nc, cl, g, n).astype(F32)
    Cr = Cm.reshape(b, nc, cl, g, n).astype(F32)
    dA = dtr * A.reshape(g, hg)                      # log-decay
    cs = jnp.cumsum(dA, axis=2)                      # inclusive cumsum

    xdt = xr * dtr[..., None]                        # dt-weighted inputs

    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), F32)
    causal = jnp.tril(jnp.ones((cl, cl)))[None, :, :, None, None]

    def chunk_body(state, inputs):
        xc, csc, Bc, Cc = inputs   # (B,cl,G,Hg,P) (B,cl,G,Hg) (B,cl,G,N) x2
        # Intra-chunk ("diag block"): M[i,j] = (C_i.B_j) exp(cs_i-cs_j), j<=i
        Gm = jnp.einsum("bign,bjgn->bijg", Cc, Bc)
        decay = jnp.exp(csc[:, :, None] - csc[:, None, :])  # (B,i,j,G,Hg)
        M = Gm[..., None] * decay * causal
        y_intra = jnp.einsum("bijgh,bjghp->bighp", M, xc)
        # Contribution of the carried state: exp(cs_i) C_i . state
        y_inter = jnp.einsum("bign,bghpn,bigh->bighp", Cc, state,
                             jnp.exp(csc))
        # Next state: chunk-end decay of current + new outer products
        edec = jnp.exp(csc[:, -1:] - csc)            # decay j..end
        new_state = jnp.einsum("bjgn,bjghp,bjgh->bghpn", Bc, xc, edec)
        state = (jnp.exp(csc[:, -1])[..., None, None] * state + new_state)
        return state, y_intra + y_inter

    inputs = tuple(a.swapaxes(0, 1) for a in (xdt, cs, Br, Cr))
    final_state, ys = jax.lax.scan(
        chunk_body, initial_state.reshape(b, g, hg, p, n), inputs)
    y = ys.swapaxes(0, 1).reshape(b, s, h, p)
    return y.astype(x.dtype), final_state.reshape(b, h, p, n)


def gated_norm(y, z, scale, groups: int, eps: float):
    """RMSNorm of ``y * SiLU(z)`` over each of ``groups`` equal channel
    groups, with float32 statistics."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    shape = v.shape
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    return (v.reshape(shape) * scale.astype(F32)).astype(y.dtype)


def _split_xbc(cfg: ModelConfig, xbc):
    """x, B and C of the conv's output, B and C as (..., G, N)."""
    di, g, n = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state
    lead = xbc.shape[:-1]
    return (xbc[..., :di], xbc[..., di: di + g * n].reshape(lead + (g, n)),
            xbc[..., di + g * n:].reshape(lead + (g, n)))


def mamba_block(p, cfg: ModelConfig, x: jnp.ndarray,
                t: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One Mamba2 layer (train/prefill): x + Mamba2(RMSNorm(x + t)), with
    ``t`` (the Zamba2 shared block's output) entering the input only.
    x: (B, S, d)."""
    b, s, d = x.shape
    h = L.rms_norm(x if t is None else x + t, p["ln"], cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, p["wz"])
    xbc = jnp.einsum("bsd,de->bse", h, p["wxbc"])
    dt_raw = jnp.einsum("bsd,dh->bsh", h, p["wdt"])
    dt = jax.nn.softplus(dt_raw.astype(F32) + p["dt_bias"].astype(F32))
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"])
                      + p["conv_b"].astype(xbc.dtype))
    xi, Bm, Cm = _split_xbc(cfg, xbc)
    xi = constrain(xi, "dp", None, "model")
    hh, pp = cfg.ssm_heads, cfg.ssm_head_dim
    A = -jnp.exp(p["A_log"].astype(F32))
    y, _ = ssd_chunked(xi.reshape(b, s, hh, pp), dt, A, Bm, Cm,
                       cfg.ssm_chunk)
    y = y + xi.reshape(b, s, hh, pp) * p["D"][None, None, :, None].astype(
        y.dtype)
    y = gated_norm(y.reshape(b, s, cfg.d_inner), z, p["norm"],
                   cfg.ssm_ngroups, cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out"])
    return x + out


def init_ssm_cache(cfg: ModelConfig, batch: int, n_layers: int,
                   dtype=F32) -> Dict:
    """Per layer and row: the last K-1 xBC inputs, and the SSM state as
    (groups, state, heads of the group x head_dim): the last axis is the
    group's x channels in order, so the state is as wide as its lanes and
    the step reads and writes it in the layout it computes in."""
    g = cfg.ssm_ngroups
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.ssm_conv - 1, cfg.conv_dim),
                          dtype),
        "state": jnp.zeros((n_layers, batch, g, cfg.ssm_state,
                            cfg.d_inner // g), dtype),
    }


def start_fresh(fresh, conv_state, ssm_state):
    """The recurrent state with the rows that start a sequence (``fresh``,
    (B,)) taken as zero: a slot that takes a new request must not carry
    its last request's conv window and SSM state.  An elementwise select
    that XLA fuses into the step's own read of the state, so it costs no
    pass over the state of its own."""
    keep = ~fresh
    return (jnp.where(keep[:, None, None], conv_state, 0),
            jnp.where(keep[:, None, None, None], ssm_state, 0))


def mamba_decode(p, cfg: ModelConfig, x, conv_state, ssm_state, fresh=None,
                 t=None):
    """One-token Mamba2 step.  x: (B, 1, d); conv_state (B, K-1, C) holds
    the last K-1 xBC inputs; ssm_state (B, G, N, Hg*P) as
    :func:`init_ssm_cache` lays it out; ``fresh`` (B,) marks the rows at
    position 0, which start from a zero window and state; ``t`` as in
    :func:`mamba_block`.  Returns (out, new_conv, new_state)."""
    b = x.shape[0]
    if fresh is not None:
        conv_state, ssm_state = start_fresh(fresh, conv_state, ssm_state)
    h = L.rms_norm(x if t is None else x + t, p["ln"], cfg.norm_eps)[:, 0]
    z = h @ p["wz"]
    xbc = h @ p["wxbc"]
    dt = jax.nn.softplus((h @ p["wdt"]).astype(F32)
                         + p["dt_bias"].astype(F32))          # (B, H)
    window = jnp.concatenate(
        [conv_state, xbc[:, None, :].astype(conv_state.dtype)], axis=1)
    conv_out = (jnp.einsum("bkc,ck->bc", window, p["conv"].astype(F32))
                + p["conv_b"].astype(F32))
    new_conv = window[:, 1:, :]
    xi, Bm, Cm = _split_xbc(cfg, jax.nn.silu(conv_out))
    g, pp = cfg.ssm_ngroups, cfg.ssm_head_dim
    xg = xi.reshape(b, g, -1).astype(F32)                    # (B, G, Hg*P)

    def per_channel(v):       # (B, H) or (H,) per head -> per x channel
        return jnp.repeat(v, pp, axis=-1).reshape(v.shape[:-1] + (g, -1))

    A = -jnp.exp(p["A_log"].astype(F32))
    decay = per_channel(jnp.exp(dt * A))                     # (B, G, Hg*P)
    new_state = (decay[:, :, None, :] * ssm_state
                 + Bm[..., None] * (per_channel(dt) * xg)[:, :, None, :])
    y = jnp.einsum("bgn,bgnk->bgk", Cm, new_state)
    y = y + xg * per_channel(p["D"].astype(F32))
    y = y.reshape(b, cfg.d_inner).astype(x.dtype)
    y = gated_norm(y, z, p["norm"], g, cfg.norm_eps)
    out = (y @ p["out"])[:, None, :]                 # (B, 1, d)
    return x + out, new_conv, new_state


def decode_layers(layers, cfg: ModelConfig, x, conv, state, lo: int,
                  hi: int, fresh, t=None):
    """Mamba layers ``lo .. hi - 1`` of a one-token step.  ``conv`` and
    ``state`` are the stacks of every layer, (L, B, ...), carried whole:
    each layer's window and state are read and written in place, so a step
    holds one copy of the recurrent state.  ``t`` (a range of one layer
    only) as in :func:`mamba_decode`.  Returns (x, conv, state)."""
    if hi <= lo:
        return x, conv, state

    def body(i, carry):
        x, conv, state = carry
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            layers)
        x, c, s = mamba_decode(
            lp, cfg, x, jax.lax.dynamic_index_in_dim(conv, i, keepdims=False),
            jax.lax.dynamic_index_in_dim(state, i, keepdims=False), fresh, t)
        return (x, jax.lax.dynamic_update_index_in_dim(conv, c, i, 0),
                jax.lax.dynamic_update_index_in_dim(state, s, i, 0))

    return jax.lax.fori_loop(lo, hi, body, (x, conv, state))


def row_positions(cur_len, batch: int):
    """``cur_len`` (a scalar or per row) as a (B,) int32 vector."""
    return jnp.broadcast_to(
        jnp.reshape(jnp.asarray(cur_len, jnp.int32), (-1,)), (batch,))


def loss_fn(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    from repro.models import transformer as T
    x = T.embed(params, cfg, batch["tokens"])
    seq = "model" if cfg.seq_shard_activations else None
    x = constrain(x, "dp", seq, None)

    def body(x, lp):
        return mamba_block(lp, cfg, x), None

    body = T._maybe_remat(body, cfg)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = T.logits_fn(params, cfg, x)
    return L.softmax_xent(logits, batch["labels"], cfg.vocab_size)


def init_params(key, cfg: ModelConfig) -> Dict:
    d, v = cfg.d_model, cfg.padded_vocab
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    return {
        "embed": L.embed_init(ks[0], (v, d), dt),
        "layers": init_mamba(ks[1], cfg, cfg.n_layers),
        "final_norm": jnp.ones((d,), dt),
        "head": L.dense_init(ks[2], (d, v), dt, in_axis=0),
    }


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_len):
    """One greedy decode step; rows at position 0 start from a zero
    recurrent state."""
    from repro.models import transformer as T
    x = T.embed(params, cfg, tokens)
    fresh = row_positions(cur_len, x.shape[0]) == 0
    x, conv, state = decode_layers(params["layers"], cfg, x, cache["conv"],
                                   cache["state"], 0, cfg.n_layers, fresh)
    hidden = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = T.logits_fn(params, cfg, hidden)
    return logits, {"conv": conv, "state": state}
