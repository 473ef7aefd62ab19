"""Plain float32 reference of a dense decoder-only language model, and the
benchmark's random weights for it.

The forward pass follows the published description of the Qwen3 and
Mistral dense models: token embedding; per layer, RMSNorm, attention with
grouped KV heads (with RMSNorm over each query and key head where the
configuration has ``qk_norm``), rotary embedding, causal softmax and the
output projection, then RMSNorm and a SwiGLU MLP, each added to the
residual; a final RMSNorm and an untied head.  It is written in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``,
with no cache, no batching of slots and no kernels, and imports nothing of
the program.  The one departure from the published models is listed in
each configuration's file under ``departures`` and made here as well:
rotary embedding rotates interleaved pairs ``(x[2i], x[2i+1])``.

:func:`make_weights` draws the weights from the seed in one jitted call,
in bfloat16, the type they are served in.  The embedding and the head
keep ``vocab_rows`` rows (a program may pad the vocabulary): rows past
``vocab_size`` are drawn like the others, from keys of their own, so the
first ``vocab_size`` rows do not depend on the padding.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file with
    the published names."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    qk_norm: bool

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        return cls(
            n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim")
                         or c["hidden_size"] // c["num_attention_heads"]),
            d_ff=int(c["intermediate_size"]),
            vocab_size=int(c["vocab_size"]),
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            qk_norm=bool(c["qk_norm"]))


def shapes(dims: Dims, vocab_rows: int) -> Dict[str, tuple]:
    """Every weight, layers stacked on a leading axis."""
    L, d, dh = dims.n_layers, dims.d_model, dims.head_dim
    hq, hkv, f = dims.n_heads * dh, dims.n_kv_heads * dh, dims.d_ff
    out = {
        "embed": (vocab_rows, d), "head": (vocab_rows, d),
        "final_norm": (d,), "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, hq), "wk": (L, d, hkv), "wv": (L, d, hkv),
        "wo": (L, hq, d), "wg": (L, d, f), "wu": (L, d, f),
        "wd": (L, f, d),
    }
    if dims.qk_norm:
        out["q_norm"] = (L, dh)
        out["k_norm"] = (L, dh)
    return out


def _draw(key, name: str, shape: tuple, vocab_size: int):
    if name in ("embed", "head"):
        # one key for the published rows, another for any padding
        rows = jax.random.normal(key, (vocab_size,) + shape[1:], F32)
        if shape[0] > vocab_size:
            pad = jax.random.normal(jax.random.fold_in(key, 1),
                                    (shape[0] - vocab_size,) + shape[1:], F32)
            rows = jnp.concatenate([rows, pad])
        return rows * (1.0 if name == "embed" else shape[1] ** -0.5)
    if "norm" in name or name.startswith("ln"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    return jax.random.normal(key, shape, F32) * shape[-2] ** -0.5


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, dims: Dims, vocab_rows: int, layout):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(dims, vocab_rows)
                                             .items())):
        out[name] = _draw(jax.random.fold_in(key, i), name, shape,
                          dims.vocab_size).astype(jnp.bfloat16)
    return out if layout is None else layout(out)


def make_weights(dims: Dims, seed: int, vocab_rows: int,
                 layout=None) -> Dict:
    """Every weight as bfloat16, drawn from ``seed`` (any whole number,
    however large) on the default device in one jitted call.  ``layout``,
    a hashable function, rearranges them inside that call into the
    layout a program takes."""
    key = jax.random.key(int(seed) % (2 ** 63))
    return _make(key, dims, vocab_rows, layout)


# ---------------------------------------------------------------- forward


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotary embedding of ``x`` (..., S, H, Dh) over interleaved pairs."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions.astype(F32)[:, None] * inv           # (S, Dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _layer(dims: Dims, x, w, quant):
    """One decoder layer over ``x`` (B, S, d) in float32; ``quant`` rounds
    a tensor to the precision under test (the identity for the
    reference)."""
    b, s, _ = x.shape
    dh = dims.head_dim
    pos = jnp.arange(s)
    h = rms_norm(x, w["ln1"], dims.norm_eps)
    q = (h @ w["wq"]).reshape(b, s, dims.n_heads, dh)
    k = (h @ w["wk"]).reshape(b, s, dims.n_kv_heads, dh)
    v = (h @ w["wv"]).reshape(b, s, dims.n_kv_heads, dh)
    if dims.qk_norm:
        q = rms_norm(q, w["q_norm"], dims.norm_eps)
        k = rms_norm(k, w["k_norm"], dims.norm_eps)
    q = rope(q, pos, dims.rope_theta)
    k, v = quant(rope(k, pos, dims.rope_theta)), quant(v)
    g = dims.n_heads // dims.n_kv_heads
    q = q.reshape(b, s, dims.n_kv_heads, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * dh ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v)
    x = x + o.reshape(b, s, -1) @ w["wo"]
    h = rms_norm(x, w["ln2"], dims.norm_eps)
    return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]


def _identity(x):
    return x


def fp8_round(x):
    """Round to float8_e4m3fn with one scale per tensor (its largest
    magnitude at the format's largest finite value), back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def forward(weights, dims: Dims, tokens, low_precision: bool = False):
    """Logits (B, S, vocab_size) in float32 of ``tokens`` (B, S).
    ``low_precision`` runs the forward with the matrices and the K/V
    rounded to float8 (the precision control); the embedding and the norms
    stay as they are."""
    quant = fp8_round if low_precision else _identity
    layer_keys = [k for k in weights if k not in ("embed", "head",
                                                  "final_norm")]

    def up(name, a):
        a = a.astype(F32)
        return quant(a) if name.startswith("w") or name == "head" else a

    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)

        def body(x, lw):
            return _layer(dims, x, {k: up(k, v) for k, v in lw.items()},
                          quant), None

        x, _ = jax.lax.scan(body, x, {k: weights[k] for k in layer_keys})
        h = rms_norm(x, up("final_norm", weights["final_norm"]),
                     dims.norm_eps)
        head = up("head", weights["head"][: dims.vocab_size])
        return jnp.einsum("bsd,vd->bsv", h, head)


@functools.partial(jax.jit, static_argnums=(1, 4))
def _logit_gaps(weights, dims: Dims, tokens, judged, low_precision: bool):
    """For each position of ``tokens`` (B, S), the reference's best logit
    less its logit for ``judged`` (B, S), and the id that the forward puts
    first."""
    logits = forward(weights, dims, tokens, low_precision)
    best = jnp.max(logits, -1)
    safe = jnp.clip(judged, 0, dims.vocab_size - 1)
    mine = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return best - mine, jnp.argmax(logits, -1).astype(jnp.int32)


def logit_gaps(weights, dims: Dims, tokens: np.ndarray, judged: np.ndarray,
               low_precision: bool = False):
    """Host arrays of :func:`_logit_gaps`: gaps (float32) and first ids."""
    gaps, top = _logit_gaps(weights, dims, jnp.asarray(tokens, jnp.int32),
                            jnp.asarray(judged, jnp.int32), low_precision)
    return np.asarray(gaps), np.asarray(top)
