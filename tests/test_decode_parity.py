"""Decode-vs-forward logits parity: feeding tokens one at a time through
``decode_step`` (with the optimized one-hot/grouped-GQA cache path) must
reproduce the full-sequence forward's next-token logits.  This is the
strongest end-to-end correctness check of the serving path — it exercises
the KV ring buffer, RoPE position handling, GQA grouping, SSM state
updates and the hybrid shared-attention cache at once.

The dense decode has a second form, taken when ``cur_len`` is a per-row
``(B,)`` vector as the serving engines pass it (the stacked cache carried
through the layer scan): its cases feed rows at staggered positions, and
rows that share one position against the scalar form.  The smoke presets'
two KV heads are read by the ``kernels.flash_decode`` kernel, here in
blocks of 8 positions so that a row spans several and the last runs past
the cache; the ``-xla`` cases take 3 KV heads, which keep the XLA
attention."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build, transformer

T = 12


def _full_forward_logits(model, params, tokens):
    """Next-token logits at every position via the training-path forward."""
    cfg = model.cfg
    from repro.models import hybrid, ssm
    x = transformer.embed(params, cfg, tokens)
    positions = jnp.arange(x.shape[1])
    if cfg.family == "hybrid":
        hidden = hybrid.forward(params, cfg, x, positions)
    elif cfg.family == "ssm":
        def body(x, lp):
            return ssm.mamba_block(lp, cfg, x), None
        hidden, _ = jax.lax.scan(body, x, params["layers"])
        from repro.models import layers as L
        hidden = L.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    else:
        hidden = transformer.forward(params, cfg, x, positions)
    return transformer.logits_fn(params, cfg, hidden)


def _decode_logits(model, params, tokens, per_row=False):
    """Logits at every position, feeding every row at position i in step
    i: ``cur_len`` a scalar, or with ``per_row`` the same position as a
    (B,) vector."""
    cache = model.init_cache(tokens.shape[0], T + 4, dtype=jnp.float32)
    decode = jax.jit(model.decode_step) if per_row else model.decode_step
    outs = []
    for i in range(tokens.shape[1]):
        pos = jnp.asarray(i, jnp.int32)
        if per_row:
            pos = jnp.full((tokens.shape[0],), i, jnp.int32)
        logits, cache = decode(params, cache, tokens[:, i: i + 1], pos)
        outs.append(logits[:, 0])
    return jnp.stack(outs, axis=1)  # (B, T, V)


STARTS = np.array([0, 3, 0, 7])   # the step at which each row starts


def _decode_logits_staggered(model, params, tokens):
    """Logits at every position of each row, fed at its own position (a
    (B,) ``cur_len``): row r starts at step ``STARTS[r]``.  Until then it
    sits at position 0 on token 0, so its cache holds another token's K/V
    where it starts, as a reused serving slot does; once done it goes on
    past its last position, so the rows' positions differ at every
    step."""
    b, t = tokens.shape
    tok = np.asarray(tokens)
    cache = model.init_cache(b, t + STARTS.max() + 1, dtype=jnp.float32)
    out = [[None] * t for _ in range(b)]
    decode = jax.jit(model.decode_step)
    for step in range(t + STARTS.max()):
        pos = np.maximum(step - STARTS, 0)
        fed = np.where(pos < t, tok[np.arange(b), np.minimum(pos, t - 1)],
                       0)
        fed = np.where(step >= STARTS, fed, 0)
        logits, cache = decode(params, cache, jnp.asarray(fed[:, None]),
                               jnp.asarray(pos, jnp.int32))
        for r in range(b):
            if step >= STARTS[r] and pos[r] < t:
                out[r][pos[r]] = np.asarray(logits[r, 0])
    return np.stack([np.stack(row) for row in out])    # (B, T, V)


ROW_ARCHS = ["qwen3-8b", "qwen3-moe-30b-a3b"]


@pytest.mark.parametrize("arch,positions,xla", [
    *(pytest.param(a, "scalar", False, id=a)
      for a in ["qwen3-8b", "yi-34b", "mamba2-1.3b", "zamba2-7b",
                "qwen3-moe-30b-a3b"]),
    *(pytest.param(a, "staggered", False, id=f"{a}-staggered-rows")
      for a in ROW_ARCHS),
    *(pytest.param(a, "shared", False, id=f"{a}-shared-rows")
      for a in ROW_ARCHS),
    *(pytest.param(a, "staggered", True, id=f"{a}-staggered-rows-xla")
      for a in ROW_ARCHS),
    *(pytest.param(a, "shared", True, id=f"{a}-shared-rows-xla")
      for a in ROW_ARCHS),
])
def test_decode_matches_forward(arch, positions, xla, monkeypatch):
    """``scalar``: the decode's logits against the full forward's;
    ``staggered``: each row's, fed at its own position, against the full
    forward's at that position; ``shared``: every row at one position as
    a (B,) vector against the scalar form's.  ``xla``: 6 query heads over
    3 KV heads, which the per-row form attends with XLA."""
    cfg = configs.get_smoke(arch)
    if xla:
        cfg = dataclasses.replace(cfg, n_heads=6, n_kv_heads=3)
    if positions != "scalar":
        monkeypatch.setattr(transformer, "DECODE_BLOCK_KV", 8)
        assert transformer._kernel_reads_layer(cfg, jnp.float32) != xla
    # decode must use the training numerics for the comparison
    cfg = dataclasses.replace(cfg, attn_chunk=T)
    if cfg.n_experts:
        # Capacity dropping is batch-dependent by construction (GShard);
        # exact parity requires a drop-free capacity. The dropping path is
        # covered by the moe smoke tests.
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_rows = 2 if positions == "scalar" else len(STARTS)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (n_rows, T), 0, cfg.vocab_size,
        dtype=jnp.int32)
    if positions == "shared":
        dec = np.asarray(_decode_logits(model, params, tokens, per_row=True))
        ref = np.asarray(_decode_logits(model, params, tokens))
        np.testing.assert_allclose(dec, ref, atol=1e-5, rtol=1e-5)
        return
    ref = np.asarray(_full_forward_logits(model, params, tokens),
                     dtype=np.float32)
    if positions == "staggered":
        dec = _decode_logits_staggered(model, params, tokens)
    else:
        dec = np.asarray(_decode_logits(model, params, tokens),
                         dtype=np.float32)
    # compare next-token distributions position by position
    np.testing.assert_allclose(dec, ref, atol=2e-3, rtol=2e-3)


def _int8_kv_agreement(per_row: bool) -> float:
    cfg = dataclasses.replace(
        configs.get_smoke("qwen3-8b"), attn_chunk=T,
        kv_cache_dtype="int8")
    model = build(cfg)
    cfg_ref = dataclasses.replace(cfg, kv_cache_dtype="bfloat16")
    model_ref = build(cfg_ref)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, T), 0, cfg.vocab_size, dtype=jnp.int32)
    dec8 = np.asarray(_decode_logits(model, params, tokens, per_row))
    dec16 = np.asarray(_decode_logits(model_ref, params, tokens, per_row))
    return float(np.mean(dec8.argmax(-1) == dec16.argmax(-1)))


def test_decode_parity_with_int8_kv_close():
    """int8 KV quantization (the §Perf C4 knob) stays close in argmax."""
    agree = _int8_kv_agreement(per_row=False)
    assert agree >= 0.8, agree


def test_decode_parity_with_int8_kv_close_per_row():
    """The same on the per-row form, whose kernel dequantizes the int8
    cache as it reads it."""
    agree = _int8_kv_agreement(per_row=True)
    assert agree >= 0.8, agree
