"""zamba2-7b — Zamba2-7B-Instruct (arXiv:2411.15242), the published
config.json (huggingface.co/Zyphra/Zamba2-7B-Instruct).

# [hybrid] 81 Mamba2 layers (112 heads of 64, state 64, 2 B/C groups) and
# two shared attention+MLP blocks used in turn at 13 hybrid_layer_ids:
# attention over [h, e] (32 heads of 224), a GELU-gated MLP of 14,336 with
# a rank-128 adapter per site, and a linear per site.  Tied embeddings
# (the Zamba2Config default; the published config sets none).
"""
from repro.models.config import ModelConfig
import dataclasses

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    d_head=224,
    d_ff=14336,
    vocab_size=32000,
    rope_theta=10000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    ssm_state=64,
    ssm_head_dim=64,
    ssm_ngroups=2,
    ssm_chunk=256,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
)

# Reduced same-family smoke config: tiny widths/depths, one CPU train step.
SMOKE = dataclasses.replace(
    CONFIG,
    param_dtype='float32',
    remat='none',
    attn_chunk=64,
    seq_shard_activations=False,
    vocab_size=512,
    d_model=64,
    d_ff=128,
    n_layers=6,
    n_heads=4,
    n_kv_heads=4,
    d_head=32,
    ssm_state=16,
    ssm_head_dim=16,
    ssm_chunk=16,
    hybrid_layer_ids=(2, 5),
    adapter_rank=8,
)
