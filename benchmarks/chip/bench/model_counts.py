"""Operations of a dense decoder's decode step, from the configuration
file's sizes (``reference.dense_lm.Dims``) and a schedule's count of
slot-steps at each position.

A slot-step at position ``p`` processes one token against a cache that
holds positions ``0 .. p - 1``: 2 operations per matrix parameter of the
layers and the head (the published vocabulary), plus, per layer,
2 * 2 * heads * head_dim * (p + 1) for the scores and the weighted sum of
the values.  What the program adds (vocabulary padding, attention over
unused cache positions) is not counted: a share of the peak computed from
these counts cannot pass 100%.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def layer_matrix_params(dims) -> int:
    d, dh, f = dims.d_model, dims.head_dim, dims.d_ff
    hq, hkv = dims.n_heads * dh, dims.n_kv_heads * dh
    return d * hq + 2 * d * hkv + hq * d + 3 * d * f


def matrix_params(dims) -> int:
    """Matrix parameters a token passes through: the layers and the head."""
    return (dims.n_layers * layer_matrix_params(dims)
            + dims.d_model * dims.vocab_size)


def slot_step_flops(dims, position: np.ndarray) -> np.ndarray:
    attn = 4 * dims.n_heads * dims.head_dim * dims.n_layers
    return 2 * matrix_params(dims) + attn * (np.asarray(position) + 1)


def work(dims, steps: int, position_steps: np.ndarray) -> Dict[str, float]:
    """Operations of ``steps`` decode steps whose slot-steps at position
    ``p`` number ``position_steps[p]``."""
    pos = np.arange(len(position_steps), dtype=np.float64)
    n = np.asarray(position_steps, dtype=np.float64)
    return {"steps": int(steps),
            "flops": float(np.sum(n * slot_step_flops(dims, pos)))}
