"""Mamba2 SSD chunk scan — Pallas TPU kernel.

The SSD duality turns the sequential state-space recurrence into per-chunk
batched matmuls (MXU work) plus a tiny sequential inter-chunk state update.
The grid runs (B*H, n_chunks) with the chunk axis innermost; the carried
state (P x N) lives in VMEM scratch across grid steps — this exploits the
TPU's sequential grid execution exactly like flash attention's online
softmax carry.

CBP knobs: the chunk length is the cache/VMEM knob (bigger chunk = more
VMEM for the (cl x cl) decay matrix but fewer sequential steps); the
streamed x/B/C blocks double-buffer (prefetch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, o_ref,
                state_scr, *, chunk: int):
    g = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    hi = jax.lax.Precision.HIGHEST
    x = x_ref[0].astype(jnp.float32)          # (cl, P)
    dt_c = dtc_ref[0].astype(jnp.float32)     # (cl, 1)
    dt_r = dtr_ref[0].astype(jnp.float32)     # (1, cl)
    a = a_ref[g]                              # scalar A_h (negative)
    bm = b_ref[0].astype(jnp.float32)         # (cl, N)
    cm = c_ref[0].astype(jnp.float32)         # (cl, N)

    # Inclusive cumsum of dA as a lower-triangular matmul (Mosaic has no
    # cumsum), in both the column and the row layout the decays need.
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = jj <= ii
    tri = causal.astype(jnp.float32)
    cs_c = jax.lax.dot_general(
        tri, dt_c * a, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)   # (cl, 1)
    cs_r = jax.lax.dot_general(
        dt_r * a, tri, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)   # (1, cl)
    cs_last = cs_c[chunk - 1:, :]             # (1, 1)
    xdt = x * dt_c

    # Intra-chunk: M[i, j] = (C_i . B_j) * exp(cs_i - cs_j) for j <= i
    G = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # (cl, cl)
    M = jnp.where(causal, G * jnp.exp(cs_c - cs_r), 0.0)
    y = jax.lax.dot_general(
        M, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (cl, P)

    # Inter-chunk: carried state contribution + state update
    state = state_scr[...]                    # (P, N)
    y_inter = jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # (cl, P)
    y = y + y_inter * jnp.exp(cs_c)

    edec = jnp.exp(cs_last - cs_c)            # (cl, 1) decay j..chunk end
    contrib = jax.lax.dot_general(
        xdt, bm * edec, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # (P, N)
    # (1, 1) -> (1, N) -> (P, N): Mosaic broadcasts one axis at a time.
    sdec = jnp.exp(jnp.broadcast_to(cs_last, (1, state.shape[1])))
    state_scr[...] = sdec * state + contrib

    o_ref[0] = y.astype(o_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """Chunked SSD.  x: (B, S, H, P); dt: (B, S, H); A: (H,);
    Bm/Cm: (B, S, N) -> y: (B, S, H, P)."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    bh = b * h
    # (B*H, S, P); dt as (B*H, S) columns and rows; B/C shared across
    # heads: (B, S, N)
    xr = x.transpose(0, 2, 1, 3).reshape(bh, s, p)
    dtr = dt.transpose(0, 2, 1).reshape(bh, 1, s)
    dtc = dtr.reshape(bh, s, 1)
    # Per-row A_h, whole array in SMEM: a (1,) block of a (B*H,) array
    # breaks the TPU's (8, 128) tiling rule, a scalar read from SMEM does not.
    ar = jnp.broadcast_to(A[None, :], (b, h)).reshape(bh).astype(jnp.float32)

    grid = (bh, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda g, j: (g, j, 0)),
            pl.BlockSpec((1, 1, chunk), lambda g, j: (g, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            # B/C are head-shared: index the batch row b = g // h.
            pl.BlockSpec((1, chunk, n), lambda g, j: (g // h, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, j: (g // h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda g, j: (g, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtc, dtr, ar, Bm, Cm)
    return out.reshape(b, h, s, p).transpose(0, 2, 1, 3)
