"""Plain float32 reference of Zamba2 (arXiv:2411.15242), and the
benchmark's random weights for it.

The forward pass follows the published Zamba2-7B-Instruct config.json and
the block as transformers' ``modeling_zamba2`` computes it.  With e the
token's embedding and h the residual (h = e at the start), layer i is
``h <- h + Mamba2_i(RMSNorm(h + t))``, where t = 0 except at the layers
``hybrid_layer_ids``.  At the s-th of them, shared block b = s mod
``num_mem_blocks``:

* u = RMSNorm([h, e]) over 2 d channels; attention from u with
  ``num_attention_heads`` heads of ``attention_head_dim``, rotary
  embedding over all of each head, causal softmax with scale
  (head_dim / 2) ** -0.5, output projected to d;
* m = RMSNorm(that output); [g | up] = m W_gu,b + (m A_s) B_s (the site's
  rank-``adapter_rank`` adapter); t = ((GELU(g) * up) W_down,b) W_lin,s,
  with the exact erf GELU.

Mamba2: one input projection to z | xBC | dt; a causal depthwise conv of
width ``mamba_d_conv`` with a bias over xBC, then SiLU; x has
``n_mamba_heads`` heads of ``mamba_headdim``, B and C ``mamba_ngroups``
groups of ``mamba_d_state`` (heads split evenly, in order, among the
groups); dt = softplus(dt + dt_bias), A = -exp(A_log); the recurrence
``S_t = exp(dt A) S_{t-1} + dt x_t B_t``, ``y = C_t S_t + D x_t``, run one
position after another; an RMSNorm of ``y * SiLU(z)`` over each group's
channels; the output projection.  A final RMSNorm, then logits against
the tied embedding.

It is written in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``, with no cache, no batching of
slots and no kernels, and imports nothing of the program.  It computes one
layer per call, each call upcasting its own layer's weights, so that the
float32 weights of the whole model are never held at once.  The one
departure from the published model, listed in the configuration's file,
is made here as well: rotary embedding rotates interleaved pairs.

:func:`forward` gives the logits, :func:`ssm_states` each layer's SSM
state after a prefix of each row, as the serving engine holds it for a
request fed that prefix.

:func:`make_weights` draws the weights from the seed in one jitted call,
in bfloat16, the type they are served in; ``layout`` rearranges them into
the program's layout inside that call.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference.dense_lm import fp8_round, rms_norm, rope

F32 = jnp.float32

#: Precisions of :func:`forward`: the reference, the precision control
#: (float8 matrices and K/V), and bfloat16 recurrent state.
PRECISIONS = ("float32", "float8", "bf16_state")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file with
    the published names."""

    n_layers: int
    d_model: int
    sites: Tuple[int, ...]
    n_blocks: int
    adapter_rank: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    conv_width: int
    time_step_min: float
    time_step_max: float
    time_step_floor: float

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        sites = tuple(int(i) for i in c["hybrid_layer_ids"])
        kinds = c["layers_block_type"]
        if (len(kinds) != int(c["num_hidden_layers"])
                or tuple(i for i, k in enumerate(kinds)
                         if k == "hybrid") != sites):
            raise ValueError("layers_block_type disagrees with "
                             "num_hidden_layers or hybrid_layer_ids")
        dims = cls(
            n_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]), sites=sites,
            n_blocks=int(c["num_mem_blocks"]),
            adapter_rank=int(c["adapter_rank"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["attention_head_dim"]),
            d_ff=int(c["ffn_hidden_size"]),
            vocab_size=int(c["vocab_size"]),
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            ssm_heads=int(c["n_mamba_heads"]),
            ssm_head_dim=int(c["mamba_headdim"]),
            ssm_state=int(c["mamba_d_state"]),
            ssm_groups=int(c["mamba_ngroups"]),
            conv_width=int(c["mamba_d_conv"]),
            time_step_min=float(c["time_step_min"]),
            time_step_max=float(c["time_step_max"]),
            time_step_floor=float(c["time_step_floor"]))
        if dims.d_inner != int(c["mamba_expand"]) * dims.d_model:
            raise ValueError("n_mamba_heads * mamba_headdim != "
                             "mamba_expand * hidden_size")
        return dims

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def shapes(dims: Dims, vocab_rows: int) -> Dict[str, tuple]:
    """Every weight: the Mamba layers stacked on a leading axis (``m_``),
    the shared blocks (``b_``) and the sites' own weights (``s_``)."""
    L, d, f, r = dims.n_layers, dims.d_model, dims.d_ff, dims.adapter_rank
    nb, ns, h = dims.n_blocks, len(dims.sites), dims.ssm_heads
    di, c = dims.d_inner, dims.conv_dim
    hq, hkv = dims.n_heads * dims.head_dim, dims.n_kv_heads * dims.head_dim
    return {
        "embed": (vocab_rows, d), "final_norm": (d,),
        "m_ln": (L, d), "m_in": (L, d, di + c + h),
        "m_conv_w": (L, c, dims.conv_width), "m_conv_b": (L, c),
        "m_dt_bias": (L, h), "m_A_log": (L, h), "m_D": (L, h),
        "m_norm": (L, di), "m_out": (L, di, d),
        "b_ln_attn": (nb, 2 * d), "b_wq": (nb, 2 * d, hq),
        "b_wk": (nb, 2 * d, hkv), "b_wv": (nb, 2 * d, hkv),
        "b_wo": (nb, hq, d), "b_ln_mlp": (nb, d),
        "b_gate_up": (nb, d, 2 * f), "b_down": (nb, f, d),
        "s_adapter_a": (ns, d, r), "s_adapter_b": (ns, r, 2 * f),
        "s_linear": (ns, d, d),
    }


def _draw(key, name: str, shape: tuple, dims: Dims):
    if name == "embed":
        # one key for the published rows, another for any padding
        rows = jax.random.normal(key, (dims.vocab_size,) + shape[1:], F32)
        if shape[0] > dims.vocab_size:
            pad = jax.random.normal(jax.random.fold_in(key, 1),
                                    (shape[0] - dims.vocab_size,)
                                    + shape[1:], F32)
            rows = jnp.concatenate([rows, pad])
        return rows * shape[1] ** -0.5
    if name == "m_A_log":                        # A in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "m_dt_bias":                      # softplus^-1 of dt
        lo, hi = np.log(dims.time_step_min), np.log(dims.time_step_max)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, F32, lo, hi)),
                         dims.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    normal = jax.random.normal(key, shape, F32)
    if name == "m_conv_w":
        return normal * shape[-1] ** -0.5
    if name == "m_conv_b":
        return 0.1 * normal
    if "ln" in name or "norm" in name or name == "m_D":
        return 1.0 + 0.1 * normal
    return normal * shape[-2] ** -0.5


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, dims: Dims, vocab_rows: int, layout):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(dims, vocab_rows)
                                             .items())):
        out[name] = _draw(jax.random.fold_in(key, i), name, shape,
                          dims).astype(jnp.bfloat16)
    return out if layout is None else layout(out, dims)


def make_weights(dims: Dims, seed: int, vocab_rows: int,
                 layout=None) -> Dict:
    """Every weight as bfloat16, drawn from ``seed`` (any whole number,
    however large) on the default device in one jitted call.  ``layout``,
    a hashable function of the weights and ``dims``, rearranges them
    inside that call into the layout a program takes."""
    key = jax.random.key(int(seed) % (2 ** 63))
    return _make(key, dims, vocab_rows, layout)


# ---------------------------------------------------------------- forward


def _identity(x):
    return x


def _quant(precision: str):
    return fp8_round if precision == "float8" else _identity


def _up(w: Dict, quant) -> Dict:
    """A layer's weights in float32, the projection matrices through
    ``quant``."""
    return {k: quant(v.astype(F32)) if v.ndim >= 2 and "conv" not in k
            else v.astype(F32) for k, v in w.items()}


def _group_norm(y, z, scale, groups: int, eps: float):
    v = y * jax.nn.silu(z)
    shape = v.shape
    v = v.reshape(shape[:-1] + (groups, shape[-1] // groups))
    return rms_norm(v, 1.0, eps).reshape(shape) * scale


@functools.partial(jax.jit, static_argnums=(1, 4))
def _mamba(w, dims: Dims, h, t, precision: str, upto):
    """One Mamba layer over ``h`` (B, S, d), ``t`` entering its input.
    Returns the layer's output and each row's SSM state (B, H, P, N) after
    its first ``upto`` (B,) positions (zero where ``upto`` is 0)."""
    w = _up(w, _quant(precision))
    b, s, _ = h.shape
    di, c, nh = dims.d_inner, dims.conv_dim, dims.ssm_heads
    g, n, p, k = dims.ssm_groups, dims.ssm_state, dims.ssm_head_dim, \
        dims.conv_width
    u = rms_norm(h + t, w["m_ln"], dims.norm_eps)
    zxbcdt = u @ w["m_in"]
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di: di + c], \
        zxbcdt[..., di + c:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j: j + s] * w["m_conv_w"][:, j] for j in range(k))
    xbc = jax.nn.silu(conv + w["m_conv_b"])
    x = xbc[..., :di].reshape(b, s, nh, p)
    heads_per_group = nh // g
    Bm = jnp.repeat(xbc[..., di: di + g * n].reshape(b, s, g, n),
                    heads_per_group, axis=2)              # (B, S, H, N)
    Cm = jnp.repeat(xbc[..., di + g * n:].reshape(b, s, g, n),
                    heads_per_group, axis=2)
    dt = jax.nn.softplus(dt + w["m_dt_bias"])             # (B, S, H)
    A = -jnp.exp(w["m_A_log"])
    def step(carry, inputs):
        state, kept = carry
        x_t, B_t, C_t, dt_t, at = inputs
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        if precision == "bf16_state":
            # reduce_precision, not a cast there and back, which the TPU
            # compiler may drop as excess precision
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        kept = jnp.where((upto == at + 1)[:, None, None, None], state, kept)
        return (state, kept), jnp.einsum("bhpn,bhn->bhp", state, C_t)

    zero = jnp.zeros((b, nh, p, n), F32)
    (_, kept), y = jax.lax.scan(
        step, (zero, zero),
        tuple(a.swapaxes(0, 1) for a in (x, Bm, Cm, dt)) + (jnp.arange(s),))
    y = y.swapaxes(0, 1) + w["m_D"][:, None] * x
    y = _group_norm(y.reshape(b, s, di), z, w["m_norm"], g, dims.norm_eps)
    return h + y @ w["m_out"], kept


@functools.partial(jax.jit, static_argnums=(2, 5))
def _site(bw, sw, dims: Dims, h, e, precision: str):
    """t of a site: its shared block ``bw`` and its own weights ``sw``."""
    quant = _quant(precision)
    bw, sw = _up(bw, quant), _up(sw, quant)
    b, s, _ = h.shape
    dh = dims.head_dim
    pos = jnp.arange(s)
    u = rms_norm(jnp.concatenate([h, e], -1), bw["b_ln_attn"],
                 dims.norm_eps)
    q = rope((u @ bw["b_wq"]).reshape(b, s, dims.n_heads, dh), pos,
             dims.rope_theta)
    kk = quant(rope((u @ bw["b_wk"]).reshape(b, s, dims.n_kv_heads, dh),
                    pos, dims.rope_theta))
    v = quant((u @ bw["b_wv"]).reshape(b, s, dims.n_kv_heads, dh))
    rep = dims.n_heads // dims.n_kv_heads
    kk, v = jnp.repeat(kk, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, kk) * (dh / 2) ** -0.5
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(scores, -1), v)
    m = rms_norm(o.reshape(b, s, -1) @ bw["b_wo"], bw["b_ln_mlp"],
                 dims.norm_eps)
    gu = m @ bw["b_gate_up"] + (m @ sw["s_adapter_a"]) @ sw["s_adapter_b"]
    gate, up = jnp.split(gu, 2, axis=-1)
    f = (jax.nn.gelu(gate, approximate=False) * up) @ bw["b_down"]
    return f @ sw["s_linear"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(final_norm, embed, dims: Dims, precision: str, h):
    hidden = rms_norm(h, final_norm.astype(F32), dims.norm_eps)
    table = _quant(precision)(embed[: dims.vocab_size].astype(F32))
    return jnp.einsum("bsd,vd->bsv", hidden, table)


def _layers(weights, dims: Dims, tokens, precision: str, upto):
    """The final residual (B, S, d) of ``tokens`` (B, S), one layer a call,
    and each layer's SSM state after each row's first ``upto`` positions,
    (L, B, H, P, N)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    upto = jnp.asarray(upto, jnp.int32)
    states = []
    with jax.default_matmul_precision("highest"):
        e = weights["embed"][jnp.asarray(tokens)].astype(F32)
        h, zero = e, jnp.zeros_like(e)
        for i in range(dims.n_layers):
            t = zero
            if i in dims.sites:
                s = dims.sites.index(i)
                bw = {k: v[s % dims.n_blocks] for k, v in weights.items()
                      if k.startswith("b_")}
                sw = {k: v[s] for k, v in weights.items()
                      if k.startswith("s_")}
                t = _site(bw, sw, dims, h, e, precision)
            h, kept = _mamba({k: v[i] for k, v in weights.items()
                              if k.startswith("m_")}, dims, h, t, precision,
                             upto)
            states.append(kept)
    return h, jnp.stack(states)


def forward(weights, dims: Dims, tokens, precision: str = "float32"):
    """Logits (B, S, vocab_size) in float32 of ``tokens`` (B, S), one layer
    a call.  ``precision`` is one of :data:`PRECISIONS`: "float8" rounds
    the matrices, the head and the K/V to float8 (the precision control;
    the embedding lookup and the norms stay as they are), "bf16_state"
    keeps the SSM state in bfloat16."""
    tokens = jnp.asarray(tokens)
    h, _ = _layers(weights, dims, tokens, precision,
                   jnp.zeros(tokens.shape[:1], jnp.int32))
    with jax.default_matmul_precision("highest"):
        return _head(weights["final_norm"], weights["embed"], dims,
                     precision, h)


def ssm_states(weights, dims: Dims, tokens: np.ndarray, upto: np.ndarray,
               precision: str = "float32") -> np.ndarray:
    """Each layer's SSM state, (L, B, H, P, N) on the host, after the first
    ``upto[b]`` positions of row b of ``tokens`` (B, S)."""
    _, states = _layers(weights, dims, jnp.asarray(tokens, jnp.int32),
                        precision, upto)
    return np.asarray(states)


@functools.partial(jax.jit, static_argnums=(2,))
def _gaps(logits, judged, vocab_size: int):
    best = jnp.max(logits, -1)
    safe = jnp.clip(judged, 0, vocab_size - 1)
    mine = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return best - mine, jnp.argmax(logits, -1).astype(jnp.int32)


def logit_gaps(weights, dims: Dims, tokens: np.ndarray, judged: np.ndarray,
               precision: str = "float32"):
    """For each position of ``tokens`` (B, S): the forward's best logit
    less its logit for ``judged`` (B, S), and the id it puts first; host
    arrays."""
    logits = forward(weights, dims, jnp.asarray(tokens, jnp.int32),
                     precision)
    gaps, top = _gaps(logits, jnp.asarray(judged, jnp.int32),
                      dims.vocab_size)
    return np.asarray(gaps), np.asarray(top)
