"""Operations and least bytes of a Zamba2 hybrid's decode step, from the
configuration file's sizes (``reference.zamba2.Dims``) and a schedule's
count of slot-steps at each position.

A slot-step at position ``p`` processes one token against K/V that hold
positions ``0 .. p - 1``:

* operations: 2 per matrix parameter it passes through (every Mamba
  layer's input and output projections; at each site the shared block's
  attention and MLP, the site's adapter and linear, so a shared block
  counts once per site; the tied head over the published vocabulary),
  plus per site 2 * 2 * heads * head_dim * (p + 1) for the scores and the
  weighted sum of the values, plus per Mamba layer 2 * conv_width *
  conv channels for the conv and 6 * heads * head_dim * state for the
  recurrence (decay, the dt x B outer product and its sum, and C . S);
* bytes: its recurrent state (every layer's SSM state and conv window,
  float32) read and written, and at each site the K and V of positions
  ``0 .. p`` (bfloat16), the new row written and the rest read.

A step also reads every weight once (bfloat16).  What the program adds
(attention over unused cache positions, empty slots, vocabulary padding)
is not counted, so a share of a peak computed from these counts cannot
pass 100%.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from reference.zamba2 import Dims, shapes

WEIGHT_BYTES = 2       # bfloat16
STATE_BYTES = 4        # float32
KV_BYTES = 2           # bfloat16


def weight_bytes(dims: Dims) -> int:
    """Every weight once, the embedding (and tied head) included."""
    return WEIGHT_BYTES * sum(int(np.prod(s)) for s in
                              shapes(dims, dims.vocab_size).values())


def matrix_params(dims: Dims) -> int:
    """Matrix parameters a token passes through."""
    d, f, r = dims.d_model, dims.d_ff, dims.adapter_rank
    hq, hkv = dims.n_heads * dims.head_dim, dims.n_kv_heads * dims.head_dim
    mamba = d * (dims.d_inner + dims.conv_dim + dims.ssm_heads) \
        + dims.d_inner * d
    block = 2 * d * (hq + 2 * hkv) + hq * d + d * 2 * f + f * d
    site = block + d * r + r * 2 * f + d * d
    return (dims.n_layers * mamba + len(dims.sites) * site
            + d * dims.vocab_size)


def state_bytes(dims: Dims) -> int:
    """One slot's recurrent state: every layer's SSM state and window."""
    per_layer = (dims.ssm_heads * dims.ssm_head_dim * dims.ssm_state
                 + (dims.conv_width - 1) * dims.conv_dim)
    return STATE_BYTES * dims.n_layers * per_layer


def kv_bytes_per_position(dims: Dims) -> int:
    """K and V of one position at every site."""
    return (len(dims.sites) * 2 * dims.n_kv_heads * dims.head_dim
            * KV_BYTES)


def slot_step_flops(dims: Dims, position: np.ndarray) -> np.ndarray:
    attn = 4 * dims.n_heads * dims.head_dim * len(dims.sites)
    ssm = dims.n_layers * (
        2 * dims.conv_width * dims.conv_dim
        + 6 * dims.ssm_heads * dims.ssm_head_dim * dims.ssm_state)
    return 2 * matrix_params(dims) + ssm + attn * (np.asarray(position) + 1)


def slot_step_bytes(dims: Dims, position: np.ndarray) -> np.ndarray:
    return (2 * state_bytes(dims)
            + kv_bytes_per_position(dims) * (np.asarray(position) + 1))


def work(dims: Dims, steps: int, position_steps: np.ndarray
         ) -> Dict[str, float]:
    """Operations and least bytes of ``steps`` decode steps whose
    slot-steps at position ``p`` number ``position_steps[p]``."""
    pos = np.arange(len(position_steps), dtype=np.float64)
    n = np.asarray(position_steps, dtype=np.float64)
    return {"steps": int(steps),
            "flops": float(np.sum(n * slot_step_flops(dims, pos))),
            "bytes": float(steps * weight_bytes(dims)
                           + np.sum(n * slot_step_bytes(dims, pos)))}
