"""Flash decode — one-token grouped-query attention of every row at its own
position, reading one layer of a stacked ``(L, B, Smax, Hkv, Dh)`` KV cache
where it lies.

Decode is the memory-roofline case (the cache streams through VMEM once
per token), so the CBP knobs bind differently than in prefill:
``block_kv`` sets the streaming granularity (prefetch depth ~ one block in
flight), and blocks entirely past a row's length are neither read nor
computed — their index map repeats the row's last needed block, so the
pipeline starts no DMA for them, and ``pl.when`` skips their arithmetic.

The layer index and the rows' lengths arrive by scalar prefetch (SMEM) and
pick the block each DMA reads, so a decode that carries its stacked caches
through the layer scan attends over ``cache[layer]`` without copying the
layer out (XLA on a TPU does not fuse a dynamic slice of the layer axis
into a batched dot: it copies the layer first).

Grid: (B, Smax / block_kv), positions innermost, online-softmax scratch
carries (m, l, acc) per query head.  A block is ``block_kv`` positions of
one row as a ``(block_kv * Hkv, Dh)`` plane, into which ``(Smax, Hkv, Dh)``
folds as a bitcast of the TPU's tiles for the head counts and dtypes that
``transformer._kernel_reads_layer`` names (for others XLA would lay the
cache out anew first, and the dense decode keeps its XLA attention).
Every query head is scored against every row of the plane and the columns
of other KV heads are masked out with the positions past the row's length,
so one softmax and one matmul per block give each query head the grouped
result (the MXU has room for the Hkv-fold work; the HBM read bounds it).
Scores are taken in the cache's dtype and scaled and softmaxed in float32,
as ``models.attention.decode_attention_gqa`` does; the probabilities enter
the value matmul in the cache's dtype before the final division.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, n_kv: int, block_kv: int, scale: float,
                   kv_scale: Optional[float], ragged: bool):
    del layer_ref                                   # used by the index maps
    j = pl.program_id(1)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_kv < length)
    def _body():
        q = q_ref[0]                                # (Hq, Dh)
        k = k_ref[0, 0]                             # (block_kv * Hkv, Dh)
        v = v_ref[0, 0]
        if kv_scale is not None:                    # int8 cache
            k = k.astype(jnp.bfloat16) * kv_scale
            v = v.astype(jnp.bfloat16) * kv_scale
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s.astype(jnp.result_type(q.dtype, k.dtype)).astype(
            jnp.float32) * scale                    # (Hq, block_kv * Hkv)
        hq, rows = s.shape
        valid = (length - j * block_kv) * n_kv      # valid rows of the block
        col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0) // (hq // n_kv)
        s = jnp.where((col % n_kv == head) & (col < valid), s, NEG_INF)
        if ragged:   # the last block runs past the cache: zero what it holds
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            v = jnp.where(row < valid, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_decode(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                 layer, lengths, *, block_kv: int = 512,
                 kv_scale: Optional[float] = None,
                 interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Dh); caches: (L, B, Smax, Hkv, Dh); layer: () int;
    lengths: () or (B,) positions each row attends to, at least 1.
    ``block_kv`` positions a block (cut to Smax).  ``kv_scale`` dequantizes
    an int8 cache to bfloat16.  Returns (B, Hq, Dh) in the dtype of the
    dequantized cache."""
    n_layers, b, smax, hkv, dh = k_cache.shape
    hq = q.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    block_kv = min(block_kv, smax)
    rows = block_kv * hkv
    assert rows % 8 == 0 or block_kv == smax, (block_kv, hkv)
    n_blocks = pl.cdiv(smax, block_kv)
    cache_dtype = jnp.bfloat16 if kv_scale is not None else k_cache.dtype

    def kv_index(i, j, layer, lens):
        last = jnp.maximum(lens[i] - 1, 0) // block_kv
        return layer[0], i, jnp.minimum(j, last), 0

    kv_spec = pl.BlockSpec((1, 1, rows, dh), kv_index)
    q_spec = pl.BlockSpec((1, hq, dh), lambda i, j, layer, lens: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, n_blocks),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((hq, 1), jnp.float32),
                        pltpu.VMEM((hq, 1), jnp.float32),
                        pltpu.VMEM((hq, dh), jnp.float32)])
    kernel = functools.partial(
        _decode_kernel, n_kv=hkv, block_kv=block_kv, scale=dh ** -0.5,
        kv_scale=kv_scale, ragged=smax % block_kv != 0)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, dh), cache_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="flash_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,)), q,
      k_cache.reshape(n_layers, b, smax * hkv, dh),
      v_cache.reshape(n_layers, b, smax * hkv, dh))
