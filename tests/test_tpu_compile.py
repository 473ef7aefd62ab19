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
    cache = (8, QWEN.n_kv_heads, 4096, QWEN.d_head)
    _compile(flash_decode,
             [((8, QWEN.n_kv_heads, QWEN.d_head), jnp.bfloat16),
              (cache, jnp.bfloat16), (cache, jnp.bfloat16),
              ((), jnp.int32)], one_chip)


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
