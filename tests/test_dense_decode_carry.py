"""The dense decode keeps one stacked KV cache a step: its per-row form
carries the caches through the layer scan and writes each layer's new rows
into them in place (``transformer._decode_layers_rows``), and the serving
engine's interval carries that one buffer through its own scan and the
``lax.cond`` around each step.

Checked on the program XLA-CPU compiles, at tiny dims, for the jitted,
donated per-row ``decode_step`` and for ``JitServingEngine``'s interval:

* no ``copy`` whose result has the whole stacked cache's shape;
* no while loop (the layer scan, the interval's scan, the admission loop)
  whose carried tuple holds a stacked cache twice: once as an input read
  and again as a separate output (the form in which the scan took the
  caches as xs and gave them back as ys).

The caches are float32 here: XLA-CPU widens a bfloat16 scatter to float32
around the whole buffer, a conversion the TPU does not make, so a bfloat16
cache would show copies that say nothing about the chip.  What the TPU
compiles at the serving cell's widths is checked by
``tests/test_tpu_compile.py``.  The smoke preset's two KV heads are read by
``kernels.flash_decode`` (interpreted here); the ``xla`` cases take 3,
which keep the XLA attention.  The kernel is checked here against its
oracle too.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels.flash_decode.kernel import flash_decode
from repro.kernels.flash_decode.ref import decode_ref
from repro.models import build
from repro.models.transformer import KV_INT8_SCALE
from repro.serving import EngineConfig, JitServingEngine, Request

B, S = 4, 32


def _model(xla=False):
    cfg = dataclasses.replace(configs.get_smoke("qwen3-8b"),
                              kv_cache_dtype="float32")
    if xla:
        cfg = dataclasses.replace(cfg, n_heads=6, n_kv_heads=3)
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _stacked(model, batch, max_len):
    cache = jax.eval_shape(lambda: model.init_cache(
        batch, max_len, dtype=jnp.float32))
    return "f32[%s]" % ",".join(map(str, cache["k"].shape))


def _copies_and_loops(text, shape):
    copies = re.findall(rf"= {re.escape(shape)}{{[^}}]*}} copy\(", text)
    loops = [t.count(shape + "{")
             for t in re.findall(r"= \((.*?)\) while\(", text)]
    return copies, loops


def test_per_row_decode_step_keeps_one_cache():
    _per_row_decode_step_keeps_one_cache(xla=False)


def test_per_row_decode_step_keeps_one_cache_with_xla_attention():
    _per_row_decode_step_keeps_one_cache(xla=True)


def _per_row_decode_step_keeps_one_cache(xla):
    model, params = _model(xla)
    cache = model.init_cache(B, S, dtype=jnp.float32)
    text = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, jnp.zeros((B, 1), jnp.int32),
        jnp.arange(B, dtype=jnp.int32)).compile().as_text()
    copies, loops = _copies_and_loops(text, _stacked(model, B, S))
    assert not copies
    assert loops and max(loops) == 2, loops      # K and V, once each


def test_engine_interval_keeps_one_cache():
    _engine_interval_keeps_one_cache(xla=False)


def test_engine_interval_keeps_one_cache_with_xla_attention():
    _engine_interval_keeps_one_cache(xla=True)


def _engine_interval_keeps_one_cache(xla):
    model, params = _model(xla)
    ecfg = EngineConfig(batch_slots=B, max_len=S, page_tokens=4,
                        total_pages=16, reconfig_every_steps=4)
    eng = JitServingEngine(model, params, 2, ecfg)
    rng = np.random.default_rng(0)
    reqs = [Request(stream=i % 2, prompt=rng.integers(
        1, 100, size=3).astype(np.int32), max_new_tokens=4)
        for i in range(6)]
    state = eng._build_state(reqs)
    text = eng._interval_jit.lower(state, params, jnp.int32(64)
                                   ).compile().as_text()
    copies, loops = _copies_and_loops(text, _stacked(model, B, S))
    assert not copies
    assert loops and max(loops) == 2, loops


@pytest.mark.parametrize("dtype,layer,lengths,block_kv", [
    # one block, the whole row
    pytest.param(jnp.float32, 0, [1, 5, 16, 9], 512, id="float32-0-lengths0"),
    pytest.param(jnp.bfloat16, 2, [16, 1, 3, 12], 512,
                 id="bfloat16-2-lengths1"),
    pytest.param(jnp.int8, 1, [7, 16, 2, 1], 512, id="int8-1-lengths2"),
    # four blocks, those past a row's length skipped
    pytest.param(jnp.float32, 0, [1, 5, 16, 9], 4, id="float32-blocks4"),
    pytest.param(jnp.bfloat16, 2, [16, 1, 3, 12], 4, id="bfloat16-blocks4"),
    pytest.param(jnp.int8, 1, [7, 16, 2, 1], 4, id="int8-blocks4"),
    # the last block runs past the cache
    pytest.param(jnp.float32, 1, [13, 16, 12, 3], 12, id="float32-ragged"),
    pytest.param(jnp.bfloat16, 0, [16, 14, 1, 12], 12,
                 id="bfloat16-ragged"),
])
def test_layer_decode_matches_its_oracle(dtype, layer, lengths, block_kv):
    """Every row attends to its own first ``lengths[b]`` positions of one
    layer; what lies past them (here values of 1e4) is never read."""
    n_layers, b, smax, hkv, g, dh = 3, 4, 16, 2, 3, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, hkv * g, dh), jnp.float32)
    kv = [jax.random.normal(k, (n_layers, b, smax, hkv, dh), jnp.float32)
          for k in ks[1:]]
    past = jnp.arange(smax)[None, None, :, None, None] >= jnp.asarray(
        lengths)[None, :, None, None, None]
    kv = [jnp.where(past, 1e4, c) for c in kv]
    kv_scale = None
    if dtype == jnp.int8:
        kv_scale = KV_INT8_SCALE
        kv = [jnp.clip(jnp.round(c / kv_scale), -127, 127).astype(jnp.int8)
              for c in kv]
        deq = [c.astype(jnp.bfloat16) * kv_scale for c in kv]
        q = q.astype(jnp.bfloat16)
    else:
        kv = [c.astype(dtype) for c in kv]
        deq = kv
        q = q.astype(dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    out = flash_decode(q, *kv, jnp.int32(layer), lens, block_kv=block_kv,
                       kv_scale=kv_scale, interpret=True)
    ref = decode_ref(q, *deq, layer, lens)
    assert out.dtype == ref.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
