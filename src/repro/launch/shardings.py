"""PartitionSpec assignment for parameters, optimizer state, caches and
batches.

Rules are *leaf-name based* and rank-aware so the same table covers stacked
(``(L, ...)``) and unstacked (hybrid shared block) parameters:

  wq / wg / wu / wi / wxbc / wz / wdt -> shard LAST dim over "model"
        (query heads / d_ff / ssm channels; column-parallel)
  wo / wd / out                      -> shard dim -2 over "model"
        (row-parallel: contraction dim sharded, output partial-summed)
  wk / wv / router / norms / biases  -> replicated (GQA KV replication)
  moe wg/wu/wd (rank 4)              -> shard EXPERT dim over "model" (EP)
  embed (V, d)                       -> shard d (gather stays local)
  head (d, V)                        -> shard V (vocab-parallel logits)
  A_log / D / dt_bias / norm (rank 2)-> shard last (ssm heads/channels)

Batches shard over the DP axes; decode KV caches shard the *sequence* dim
over "model" (split-KV decode) and SSM states shard their channels (heads
x head_dim).  ``wxbc`` is x, B and C side by side, sharded as one block:
see ``repro.models.ssm`` for the reshard that costs on a mesh.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.optim.optimizers import OptState

LAST = {"wg", "wu", "wi", "wxbc", "wz", "wdt", "embed"}
ROW = {"wo", "wd", "out"}
REPL = {"wk", "wv", "router", "ln", "ln1", "ln2", "lnx", "q_norm",
        "k_norm", "final_norm", "enc_norm", "dt_bias_repl"}
VEC_LAST = {"A_log", "D", "dt_bias", "norm", "conv"}


def dp_axes_for(cfg: ModelConfig):
    if cfg.pure_dp:
        return ("pod", "data", "model")
    return ("pod", "data")


def _dp(mesh, cfg: Optional[ModelConfig] = None) -> Optional[tuple]:
    wanted = dp_axes_for(cfg) if cfg is not None else ("pod", "data")
    axes = tuple(a for a in wanted if a in mesh.axis_names)
    return axes if axes else None


def _mdl(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def param_spec_for(path: tuple, leaf, cfg: ModelConfig, mesh) -> P:
    name = None
    for entry in reversed(path):
        if hasattr(entry, "key"):
            name = entry.key
            break
    m = _mdl(mesh)
    rank = len(leaf.shape)
    if m is None or cfg.pure_dp:
        return P(*([None] * rank))
    heads_ok = cfg.heads_shardable

    if name == "wq":
        return P(*([None] * (rank - 1)), m if heads_ok else None)
    if name == "wo":
        spec = [None] * rank
        if heads_ok:
            spec[rank - 2] = m
        return P(*spec)
    if name in ("wk", "wv"):
        return P(*([None] * rank))
    if name in ("wg", "wu", "wd") and rank == 4:   # MoE experts
        if cfg.moe_ep:
            return P(None, m, None, None)          # EP over experts
        dat = "data" if "data" in mesh.axis_names else None
        if name == "wd":                           # (L, E, f, d)
            return P(None, None, m, dat)
        return P(None, None, dat, m)               # TP(f) x FSDP(d)
    if name in LAST:
        if name == "embed":
            return P(None, m)  # (V, d): shard d -> local gather
        return P(*([None] * (rank - 1)), m)
    if name in ROW:
        spec = [None] * rank
        spec[rank - 2] = m
        return P(*spec)
    if name == "head":
        return P(None, m)
    if name in VEC_LAST:
        if name == "conv":                          # (L, di, K)
            spec = [None] * rank
            spec[rank - 2] = m
            return P(*spec)
        if name == "norm" and rank >= 2:            # (L, di)
            return P(*([None] * (rank - 1)), m)
        if name in ("A_log", "D", "dt_bias") and rank >= 1:
            return P(*([None] * (rank - 1)), m)
    return P(*([None] * rank))


def _validated(spec: P, leaf, mesh) -> P:
    """Drop axes whose mesh size does not divide the dim (reduced smoke
    configs and elastic odd-sized meshes)."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        dim = leaf.shape[i] if i < len(leaf.shape) else 0
        out.append(entry if dim % n == 0 and dim >= n else None)
    return P(*out)


def param_specs(cfg: ModelConfig, params_shape, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _validated(
            param_spec_for(path, leaf, cfg, mesh), leaf, mesh),
        params_shape)


def _zero1(pspec: P, leaf, mesh) -> P:
    """ZeRO-1: additionally shard optimizer state over the "data" axis on
    the first unsharded dim that divides (the update is elementwise, so
    any layout is local; the only cost is the per-step master->param
    all-gather over "data")."""
    if "data" not in mesh.axis_names:
        return pspec
    n = mesh.shape["data"]
    spec = list(pspec) + [None] * (len(leaf.shape) - len(pspec))
    used = {a for s in spec if s for a in
            (s if isinstance(s, tuple) else (s,))}
    if "data" in used:   # already FSDP-sharded over data (grok experts)
        return pspec
    best = -1
    for i, (dim, s) in enumerate(zip(leaf.shape, spec)):
        if s is None and dim % n == 0 and dim >= n:
            if best < 0 or dim > leaf.shape[best]:
                best = i
    if best >= 0:
        spec[best] = "data"
    return P(*spec)


def opt_state_specs(cfg: ModelConfig, opt_shape: OptState, params_shape,
                    mesh, kind: str) -> OptState:
    """Optimizer state mirrors parameter sharding + ZeRO-1 over "data";
    adafactor factored moments drop the reduced dim from the spec."""
    pspecs = param_specs(cfg, params_shape, mesh)

    if kind == "sgd":
        return OptState(P(), None, None, None)

    zspecs = jax.tree.map(
        lambda s, l: _zero1(s, l, mesh), pspecs, params_shape,
        is_leaf=lambda x: isinstance(x, P))

    if kind == "adamw":
        return OptState(
            step=P(), master=zspecs,
            m=zspecs, v=zspecs)

    # adafactor: v leaves are tuples (vr, vc) or (vfull,)
    def v_spec(pspec: P, vleaf):
        if len(vleaf) == 2:
            vr = P(*pspec[:-1])
            vc = P(*(pspec[:-2] + (pspec[-1],)))
            return (_zero1(vr, vleaf[0], mesh), _zero1(vc, vleaf[1], mesh))
        return (_zero1(pspec, vleaf[0], mesh),)

    is_v = lambda x: isinstance(x, tuple) and not isinstance(x, P) and all(
        hasattr(e, "shape") for e in x)
    v = jax.tree.map(v_spec, pspecs, opt_shape.v,
                     is_leaf=lambda x: isinstance(x, P) or is_v(x))
    return OptState(step=P(), master=zspecs, m=None, v=v)


def _best_dp_subset(mesh, cfg, b: int) -> Optional[tuple]:
    """Largest prefix of the DP axes whose product divides the batch."""
    axes = list(_dp(mesh, cfg) or ())
    while axes:
        n = int(np.prod([mesh.shape[a] for a in axes]))
        if b % n == 0 and b >= n:
            return tuple(axes)
        axes.pop()
    return None


def batch_specs(cfg: ModelConfig, batch_shape: Dict, mesh) -> Dict:
    def one(leaf):
        if leaf.ndim == 0:
            return P()
        lead = _best_dp_subset(mesh, cfg, leaf.shape[0])
        return P(lead, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(one, batch_shape)


def cache_specs(cfg: ModelConfig, cache_shape: Dict, mesh) -> Dict:
    m = _mdl(mesh) if not cfg.pure_dp else None

    def one(path, leaf):
        # the hybrid's per-site K/V are lists: the name is the list's key
        keys = [k.key for k in path if hasattr(k, "key")]
        name = keys[-1] if keys else ""
        if leaf.ndim == 0:
            return P()
        spec = [None] * leaf.ndim
        b = leaf.shape[1] if leaf.ndim > 1 else 0
        spec[1] = _best_dp_subset(mesh, cfg, b) if b else None
        if name in ("k", "v", "xk", "xv") and leaf.ndim == 5:
            spec[2] = m              # (L|1, B, Smax, Hkv, Dh): sequence
        elif name == "state" and leaf.ndim == 5:
            spec[4] = m              # (L, B, G, N, Hg*P): shard channels
        elif name == "conv" and leaf.ndim == 4:
            spec[3] = m              # (L, B, K-1, di): shard channels
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def named(tree, mesh):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, P))
