"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
shape/dtype sweeps, and hypothesis property tests (assignment requirement:
"for each Pallas kernel, sweep shapes/dtypes and assert_allclose against
the ref.py pure-jnp oracle")."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.cbp_matmul.kernel import cbp_matmul, vmem_footprint_bytes
from repro.kernels.cbp_matmul.ref import matmul_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_decode.kernel import flash_decode
from repro.kernels.flash_decode.ref import decode_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

# Interpret-mode Pallas emulation is slow on CPU — the whole file sits in
# the slow tier (deselected by default, run by CI and -m "slow or not slow").
pytestmark = pytest.mark.slow

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(rng, b, h, s, d, dtype):
    ks = jax.random.split(rng, 3)
    return tuple(
        jax.random.normal(k, (b, h, s, d), jnp.float32).astype(dtype)
        for k in ks)


# ------------------------- flash attention ------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 1, 128, 64), (2, 3, 256, 64), (1, 2, 512, 128), (2, 1, 256, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(dtype, shape, causal):
    b, h, s, d = shape
    q, k, v = _qkv(jax.random.PRNGKey(0), b, h, s, d, dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=64,
                              block_kv=64, interpret=True)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=causal)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref, atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("block_q,block_kv", [(32, 64), (64, 32),
                                              (128, 128), (64, 128)])
def test_flash_attention_block_invariance(block_q, block_kv):
    """CBP VMEM-knob settings change scheduling, never results."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 2, 256, 64, jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    out = flash_attention_fwd(q, k, v, causal=True, block_q=block_q,
                              block_kv=block_kv, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(
    s_blocks=st.integers(1, 4),
    h=st.integers(1, 3),
    seed=st.integers(0, 1000),
)
def test_flash_attention_property(s_blocks, h, seed):
    s = 64 * s_blocks
    q, k, v = _qkv(jax.random.PRNGKey(seed), 1, h, s, 32, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=True, block_q=64,
                              block_kv=64, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


# --------------------------- flash decode -------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("smax,cur_len", [(256, 256), (256, 100),
                                          (512, 1), (512, 511), (320, 300)])
def test_flash_decode_matches_ref(dtype, smax, cur_len):
    """Grouped heads (8 query heads over 4 KV heads), one layer of a
    stacked cache, rows at lengths ``cur_len`` and about half of it; with
    320 positions the last 128-position block runs past the cache."""
    rng = jax.random.PRNGKey(2)
    n_layers, b, hkv, g, d = 2, 2, 4, 2, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, hkv * g, d), jnp.float32).astype(dtype)
    kc, vc = (jax.random.normal(k, (n_layers, b, smax, hkv, d),
                                jnp.float32).astype(dtype) for k in ks[1:])
    lens = jnp.asarray([cur_len, max(cur_len // 2, 1)], jnp.int32)
    out = flash_decode(q, kc, vc, jnp.int32(1), lens, block_kv=128,
                       interpret=True)
    ref = decode_ref(q.astype(jnp.float32), kc.astype(jnp.float32),
                     vc.astype(jnp.float32), 1, lens)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref, atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_decode_ignores_cache_tail():
    """Positions >= cur_len must not influence the output (ring-buffer
    garbage safety)."""
    rng = jax.random.PRNGKey(3)
    b, h, smax, d = 1, 2, 256, 32
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (1, b, smax, h, d))
    vc = jax.random.normal(ks[2], (1, b, smax, h, d))
    out1 = flash_decode(q, kc, vc, 0, jnp.asarray(77), block_kv=64,
                        interpret=True)
    kc2 = kc.at[:, :, 77:].set(1e6)
    vc2 = vc.at[:, :, 77:].set(-1e6)
    out2 = flash_decode(q, kc2, vc2, 0, jnp.asarray(77), block_kv=64,
                        interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ----------------------------- SSD scan ---------------------------- #


def _ssd_inputs(rng, b, s, h, p, n, dtype=jnp.float32):
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    return (x.astype(dtype), dt.astype(dtype), A, Bm.astype(dtype),
            Cm.astype(dtype))


@pytest.mark.parametrize("shape", [
    (1, 64, 1, 8, 8), (2, 128, 3, 8, 16), (1, 256, 2, 16, 16),
])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_ssd_scan_matches_sequential_ref(shape, chunk):
    b, s, h, p, n = shape
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(4), b, s, h, p, n)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_ssd_model_path_matches_ref():
    """The model's chunked jnp implementation is the same math."""
    from repro.models.ssm import ssd_chunked
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(5), 2, 128, 4, 8, 16)
    y, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, ref, atol=2e-4, rtol=2e-4)


@settings(max_examples=8, deadline=None)
@given(chunk_pow=st.integers(4, 6), seed=st.integers(0, 500))
def test_ssd_chunk_invariance(chunk_pow, seed):
    """Chunk length is a pure scheduling knob (CBP VMEM partition)."""
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(seed), 1, 128, 2, 8, 8)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=2 ** chunk_pow, interpret=True)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(out, ref, atol=3e-4, rtol=3e-4)


# ---------------------------- cbp matmul --------------------------- #


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("blocks", [(64, 64, 64), (128, 64, 32),
                                    (32, 128, 64)])
def test_cbp_matmul_matches_ref(dtype, blocks):
    bm, bn, bk = blocks
    rng = jax.random.PRNGKey(6)
    k1, k2 = jax.random.split(rng)
    a = jax.random.normal(k1, (256, 128), jnp.float32).astype(dtype)
    b = jax.random.normal(k2, (128, 256), jnp.float32).astype(dtype)
    out = cbp_matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                     interpret=True)
    ref = matmul_ref(a, b)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("shape", [(97, 53, 160), (130, 70, 96)])
def test_cbp_matmul_pad_aware_planned_blocks(shape):
    """Prime/odd dims: the pad-aware planner returns blocks tiling
    ``ceil(dim / block) * block``; the kernel zero-pads the operands to
    that extent (exact for a matmul) and slices the result back."""
    from repro.runtime.cbp_runtime import plan_matmul_blocks

    m, n, k = shape
    bm, bn, bk = plan_matmul_blocks(m, n, k, dtype_bytes=4)
    assert bm % 8 == 0 or bm >= m  # snapped or full-extent tiling
    rng = jax.random.PRNGKey(9)
    k1, k2 = jax.random.split(rng)
    a = jax.random.normal(k1, (m, k), jnp.float32)
    b = jax.random.normal(k2, (k, n), jnp.float32)
    out = cbp_matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                     interpret=True)
    assert out.shape == (m, n)
    np.testing.assert_allclose(out, matmul_ref(a, b), atol=1e-4, rtol=1e-4)


def test_vmem_footprint_monotone():
    f1 = vmem_footprint_bytes(64, 64, 64)
    f2 = vmem_footprint_bytes(128, 128, 128)
    assert f2 > f1
    assert f1 > 0
