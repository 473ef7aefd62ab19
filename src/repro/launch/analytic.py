"""Analytic per-device memory model (no compilation needed)."""
from __future__ import annotations

from typing import Dict


def analytic_memory(cfg, spec, chips: int, optimizer: str) -> Dict:
    """Ground-truth per-device residency in bytes (native TPU dtypes —
    the CPU backend's memory_analysis inflates bf16 buffers to f32 around
    collectives/updates, so this analytic model is the capacity proof and
    memory_analysis is corroborating evidence; both are recorded)."""
    n = cfg.param_count()
    mdl = max(cfg.mesh_model, 1)
    params_b = 2.0 * n / (mdl if not cfg.pure_dp else chips // 1)
    if cfg.pure_dp:
        params_b = 2.0 * n  # replicated
    out = {"params_bytes": params_b}
    if spec.kind == "train":
        if optimizer == "adamw":
            opt = 12.0 * n            # f32 master+m+v
        else:
            opt = 4.2 * n             # f32 master + factored moments
        out["opt_bytes"] = opt / chips  # ZeRO-1 over data x model
        out["grads_bytes"] = 2.0 * n / mdl
    if spec.kind == "decode":
        sites = cfg.n_layers
        if cfg.family == "hybrid":
            sites = len(cfg.hybrid_layer_ids)
        kv = 0.0
        if cfg.family not in ("ssm",):
            kv = (2.0 * sites * spec.global_batch * spec.seq_len
                  * cfg.n_kv_heads * cfg.head_dim * 2.0)
            if cfg.family == "encdec":
                kv *= 2.0  # cross-attention K/V
        state = 0.0
        if cfg.family in ("ssm", "hybrid"):
            state = (4.0 * cfg.n_layers * spec.global_batch
                     * (cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state
                        + (cfg.ssm_conv - 1) * cfg.conv_dim))
        out["kv_cache_bytes"] = (kv + state) / chips
    out["total_bytes"] = float(sum(out.values()))
    return out

