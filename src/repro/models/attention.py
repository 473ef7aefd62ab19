"""GQA attention: training (chunked causal), prefill, and decode paths.

Sharding contract (see launch/shardings.py):
  * query heads are sharded over the "model" mesh axis when divisible
    (padded up when close — yi-34b pads 56->64); KV heads are replicated
    per model shard (GQA-natural tensor parallelism — the kv projection is
    tiny, so each shard computes all kv heads and attends with its q-head
    slice).
  * decode shards the KV-cache *sequence* over the "model" axis instead
    (split-KV flash decode at mesh scale — works for any head count); the
    softmax over the sharded axis lowers to a small all-reduce pair.

On real TPU hardware the chunked-causal path is replaced by the Pallas
flash-attention kernel in ``repro.kernels.flash_attention`` (true triangular
schedule — XLA's dense formulation below computes masked chunk pairs too);
the jnp path remains the oracle and the CPU/dry-run implementation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, Hkv, Dh) -> (B, S, Hkv*n_rep, Dh)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     chunk: int = 1024, causal: bool = True,
                     remat_chunk: bool = True,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Chunked attention.  q: (B, Sq, H, Dh); k/v: (B, Sk, H, Dh).

    Scores are computed q-chunk at a time so the live score buffer is
    (B, H, chunk, Sk) — flash-attention-shaped memory behaviour under XLA.
    ``remat_chunk`` rematerializes each chunk's scores/probs in the
    backward pass so the scan does not stack per-chunk residuals.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    chunk = min(chunk, sq)
    n_chunks = max(sq // chunk, 1)
    rem = sq - n_chunks * chunk

    kT = k.transpose(0, 2, 3, 1)  # (B, H, Dh, Sk)
    vT = v.transpose(0, 2, 1, 3)  # (B, H, Sk, Dh)

    def one_chunk(q_chunk: jnp.ndarray, start) -> jnp.ndarray:
        # q_chunk: (B, C, H, Dh)
        qT = q_chunk.transpose(0, 2, 1, 3)  # (B, H, C, Dh)
        scores = jnp.einsum(
            "bhcd,bhds->bhcs", qT, kT).astype(jnp.float32) * scale
        if causal:
            c = q_chunk.shape[1]
            qpos = start + jnp.arange(c)[:, None]
            kpos = jnp.arange(sk)[None, :]
            scores = jnp.where(kpos <= qpos, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhcs,bhsd->bhcd", probs, vT)
        return out.transpose(0, 2, 1, 3)  # (B, C, H, Dh)

    if remat_chunk:
        one_chunk = jax.checkpoint(
            one_chunk, policy=jax.checkpoint_policies.nothing_saveable)

    if n_chunks > 1:
        qs = q[:, : n_chunks * chunk].reshape(b, n_chunks, chunk, h, dh)
        starts = jnp.arange(n_chunks) * chunk

        def body(carry, xs):
            qc, st = xs
            return carry, one_chunk(qc, st)

        _, outs = jax.lax.scan(body, 0, (qs.transpose(1, 0, 2, 3, 4), starts))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(b, n_chunks * chunk, h, dh)
        if rem:
            out = jnp.concatenate(
                [out, one_chunk(q[:, n_chunks * chunk:], n_chunks * chunk)],
                axis=1)
        return out
    return one_chunk(q, 0)


def decode_attention_gqa(q: jnp.ndarray, k_cache: jnp.ndarray,
                         v_cache: jnp.ndarray, cur_len: jnp.ndarray,
                         scale: Optional[float] = None) -> jnp.ndarray:
    """Grouped-query decode WITHOUT materializing repeated KV.

    q: (B, 1, Hq, Dh); caches: (B, Smax, Hkv, Dh), Hq = G * Hkv (kv-major
    head layout, matching ``repeat_kv``).  The grouped einsum lets XLA
    broadcast KV virtually — on a 32k cache with G=4 this removes 3/4 of
    the decode HBM traffic (§Perf iteration C4).
    """
    b, one, hq, dh = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, g, dh)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    smax = k_cache.shape[1]
    mask = jnp.arange(smax)[None, :] < jnp.reshape(cur_len, (-1, 1))
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(b, 1, hq, dh)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cur_len: jnp.ndarray,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Single-position attention against a (possibly seq-sharded) cache.

    q: (B, 1, H, Dh); k_cache/v_cache: (B, Smax, H, Dh); cur_len: () or (B,)
    number of valid cache positions.  The softmax over Smax lowers to an
    all-reduce pair when Smax is sharded over the model axis.
    """
    dh = q.shape[-1]
    scale = dh ** -0.5 if scale is None else scale
    scores = jnp.einsum(
        "bqhd,bshd->bhqs", q, k_cache).astype(jnp.float32) * scale
    smax = k_cache.shape[1]
    pos = jnp.arange(smax)
    mask = pos[None, :] < jnp.reshape(cur_len, (-1, 1))  # (B, Smax)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v_cache)
