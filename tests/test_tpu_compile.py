"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Each test lowers one kernel at the widths of a model the repo serves (or,
for the boundary greedy, at the evaluator sweep's shape) and compiles it
with the TPU compiler for a ``v5e:2x2`` topology that is described, not
attached: Mosaic refuses here what it would refuse on the
chip (block shapes off the (8, 128) tiling, primitives it cannot lower,
too much VMEM).  Nothing runs, so these say nothing about results or
speed; the interpret-mode suites in ``test_kernels.py`` check results.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import cache_controller_jax as ccj
from repro.core.x64 import x64_context
from repro.kernels.cbp_matmul.kernel import cbp_matmul
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_decode.kernel import flash_decode
from repro.kernels.ssd_scan.kernel import ssd_scan

QWEN = configs.get("qwen3-8b")
MAMBA = configs.get("mamba2-1.3b")


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text   # the kernel, not an XLA fallback


def test_cbp_matmul_compiles_at_qwen3_8b_mlp_width(one_chip):
    d, f = QWEN.d_model, QWEN.d_ff
    _compile(cbp_matmul, [((d, d), jnp.bfloat16), ((d, f), jnp.bfloat16)],
             one_chip)


def test_flash_attention_compiles_at_qwen3_8b_heads(one_chip):
    shape = (1, QWEN.n_heads, 2048, QWEN.d_head)
    _compile(flash_attention_fwd, [(shape, jnp.bfloat16)] * 3, one_chip)


def test_flash_decode_compiles_at_qwen3_8b_kv_heads(one_chip):
    """One layer of a stacked 4,096-position cache, each row at its own
    length, in the dense decode's blocks (the last one runs past the
    cache)."""
    from repro.models.transformer import DECODE_BLOCK_KV

    def fn(q, k, v, layer, lengths):
        return flash_decode(q, k, v, layer, lengths,
                            block_kv=DECODE_BLOCK_KV)

    cache = (2, 8, 4096, QWEN.n_kv_heads, QWEN.d_head)
    _compile(fn, [((8, QWEN.n_heads, QWEN.d_head), jnp.bfloat16),
                  (cache, jnp.bfloat16), (cache, jnp.bfloat16),
                  ((), jnp.int32), ((8,), jnp.int32)], one_chip)


def test_ssd_scan_compiles_at_mamba2_1_3b_heads(one_chip):
    h, p, n = MAMBA.ssm_heads, MAMBA.ssm_head_dim, MAMBA.ssm_state
    s = 2048

    def fn(x, dt, A, Bm, Cm):
        return ssd_scan(x, dt, A, Bm, Cm, chunk=MAMBA.ssm_chunk)

    _compile(fn, [((1, s, h, p), jnp.bfloat16), ((1, s, h), jnp.float32),
                  ((h,), jnp.float32), ((1, s, n), jnp.bfloat16),
                  ((1, s, n), jnp.bfloat16)], one_chip)


def test_lookahead_greedy_keeps_no_curve_gathers_at_sweep_shape(one_chip):
    """The boundary greedy at the sweep's shape (56 rows, 16 clients, 256
    units, float64), compiled for a v5e: the only gathers left are the
    four ``(B, n)`` ones of ``_zero_spread``, outside the loop (the
    gathering loop compiled to 44, 36 of them in its body)."""
    B, n, U = 56, 16, 256

    def fn(curves, mins, active, remaining):
        return ccj._greedy_core(curves, mins, active, remaining,
                                total_units=U)

    shapes = [((B, n, U + 1), jnp.float64), ((B,), jnp.int32),
              ((B, n), jnp.bool_), ((B,), jnp.int32)]
    with x64_context():
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert " while(" in text
    gathers = re.findall(r"= (\w+)\[([\d,]*)\]\S* gather\(", text)
    assert len(gathers) <= 4
    assert all(dims == f"{B},{n}" for _dtype, dims in gathers), gathers


def _interval_text(sharding, n_heads, n_kv_heads, slots, max_len):
    """``JitServingEngine``'s interval at heads of 128, its other sizes
    cut, compiled for a v5e with every argument and output in its default
    layout, as the chip run holds them."""
    import numpy as np
    from jax.experimental.layout import Format, Layout

    from repro.models.config import ModelConfig
    from repro.models.model import Model
    from repro.serving import EngineConfig, JitServingEngine, Request

    cfg = ModelConfig(name="carry", n_layers=2, d_model=512, n_heads=n_heads,
                      n_kv_heads=n_kv_heads, d_head=128, d_ff=512,
                      vocab_size=512, param_dtype="bfloat16", remat="none")
    model = Model(cfg)
    eng = JitServingEngine(model, None, 4, EngineConfig(
        batch_slots=slots, max_len=max_len, page_tokens=16,
        total_pages=slots * max_len // 16, reconfig_every_steps=8))
    rng = np.random.default_rng(0)
    reqs = [Request(stream=i % 4, prompt=rng.integers(
        1, 500, size=5).astype(np.int32), max_new_tokens=8)
        for i in range(24)]
    shapes = (jax.eval_shape(lambda: eng._build_state(reqs)),
              jax.eval_shape(model.init, jax.random.PRNGKey(0)),
              jax.ShapeDtypeStruct((), jnp.int32))

    def fmt(s):
        return Format(Layout(tuple(range(len(s.shape)))), sharding)

    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=fmt(s)),
        shapes)
    outs = jax.tree.map(fmt, jax.eval_shape(eng._interval, *shapes))
    return jax.jit(eng._interval, donate_argnums=(0,),
                   out_shardings=outs).lower(*args).compile().as_text()


def _cache_moves(text, n_layers, slots, max_len, n_kv_heads):
    """Operations outside a fusion that copy, slice or update the whole
    stacked cache or one layer of it, in its own shape or as the kernel's
    (positions x KV heads) plane; and the number of stacked caches each
    while loop carries."""
    layer = f"{slots},{max_len},{n_kv_heads},128"
    plane = f"{slots},{max_len * n_kv_heads},128"
    whole = f"\\[(?:{n_layers},|1,)?(?:{layer}|{plane})\\]"
    unfused = [c for c in re.split(r"\n(?=\S)", text)
               if not c.startswith("%fused_computation")]
    moved = [line for c in unfused for line in c.splitlines()
             if re.search(rf"= bf16{whole}\S* (copy|copy-start|transpose|"
                          rf"dynamic-slice|dynamic-update-slice)\(", line)
             or re.search(rf"%\S*(copy|dynamic-(update-)?slice)\S* = "
                          rf"bf16{whole}", line)]
    loops = [t.count(f"bf16[{n_layers},{layer}]")
             for t in re.findall(r"= \((.*?)\) while\(", text)]
    return moved, loops


def test_serving_interval_reads_the_carried_kv_cache_in_place(one_chip):
    """``JitServingEngine``'s interval at the dense serving cell's head
    widths (8 KV heads of 128, 32 query heads), its other sizes cut, compiled
    for a v5e: the per-row decode carries one stacked K and one stacked V
    through the layer scan, and no copy, dynamic slice or dynamic update
    slice outside a fusion yields the whole stacked cache or one layer of
    it; each layer is read by the ``flash_decode`` kernel.  The program of
    the scan that took the caches as xs and ys copied the whole cache
    twice a step and sliced and wrote back each layer."""
    text = _interval_text(one_chip, 32, 8, slots=16, max_len=64)
    assert "tpu_custom_call" in text
    moved, loops = _cache_moves(text, 2, 16, 64, 8)
    assert not moved, moved[:2]
    assert max(loops) == 2, loops


@pytest.mark.parametrize("n_heads,n_kv_heads,max_len", [
    pytest.param(32, 8, 4096, id="kv8-4096-positions"),
    pytest.param(32, 4, 576, id="kv4-yi-9b-qwen3-moe-heads"),
    pytest.param(16, 2, 576, id="kv2-smoke-heads"),
])
def test_serving_interval_reads_the_kv_cache_through_the_kernel(
        one_chip, n_heads, n_kv_heads, max_len):
    """The same with the long contexts a one-block-per-row kernel could
    not hold in VMEM, and with the KV head counts of the other dense and
    MoE configurations: the kernel reads the cache, and nothing copies
    it."""
    text = _interval_text(one_chip, n_heads, n_kv_heads, slots=8,
                          max_len=max_len)
    assert "tpu_custom_call" in text
    moved, loops = _cache_moves(text, 2, 8, max_len, n_kv_heads)
    assert not moved, moved[:2]
    assert max(loops) == 2, loops


def test_serving_interval_with_6_kv_heads_keeps_xla_attention(one_chip):
    """With 6 KV heads the kernel's (positions x KV heads) plane is no
    bitcast of the cache, so the decode keeps XLA's attention: that copies
    each layer's slice out (a layer-shaped fusion), but no operation
    copies the whole stacked cache, and the loops carry it once."""
    text = _interval_text(one_chip, 24, 6, slots=8, max_len=64)
    assert "tpu_custom_call" not in text
    moved, loops = _cache_moves(text, 2, 8, 64, 6)
    assert not [m for m in moved if "[2,8," in m], moved[:2]
    assert max(loops) == 2, loops
