"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes,
in interpret mode (CPU container; TPU is the lowering target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.offload_copy import offload_copy_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


# ---------------------------------------------------------------------------
# offload_copy (the DSA-engine analogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 3, 4])          # sync/async/pipelined
@pytest.mark.parametrize("inject", [False, True])        # cache injection
@pytest.mark.parametrize("dtype,out_dtype", [
    ("float32", "float32"), ("float32", "bfloat16"), ("bfloat16", "float32")])
def test_offload_copy_modes(depth, inject, dtype, out_dtype, rng_key):
    x = jax.random.normal(rng_key, (512, 256)).astype(dtype)
    y, s = offload_copy_pallas(x, scale=1.5, out_dtype=out_dtype, depth=depth,
                               block_rows=128, inject=inject, interpret=True)
    yr, sr = ref.offload_copy(x, scale=1.5, out_dtype=out_dtype, inject=inject)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=1e-2, atol=1e-2)
    if inject:
        assert abs(float(s) - float(sr)) <= abs(float(sr)) * 1e-2 + 1e-2


@given(st.sampled_from([64, 128, 256]), st.sampled_from([1, 2, 4]))
@settings(max_examples=8)
def test_offload_copy_block_shape_sweep(rows, depth):
    x = jnp.arange(rows * 128, dtype=jnp.float32).reshape(rows, 128) / 1000.0
    y, _ = offload_copy_pallas(x, depth=depth, block_rows=64, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,t,h,kh,hd,causal", [
    (128, 128, 4, 4, 32, True),
    (128, 128, 4, 2, 64, True),
    (64, 128, 8, 1, 32, False),
    (256, 256, 2, 2, 128, True),
])
def test_flash_attention_shapes(s, t, h, kh, hd, causal, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (2, s, h, hd))
    k = jax.random.normal(ks[1], (2, t, kh, hd))
    v = jax.random.normal(ks[2], (2, t, kh, hd))
    o = flash_attention_pallas(q, k, v, causal=causal, block_q=64, block_k=64,
                               interpret=True)
    orf = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16(rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32)).astype(jnp.bfloat16)
    o = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)
    orf = ref.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(orf, np.float32), rtol=0.1, atol=0.1)


@given(st.sampled_from([32, 64, 128]))
@settings(max_examples=6)
def test_flash_attention_block_invariance(bq):
    ks = jax.random.split(jax.random.key(bq), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    a = flash_attention_pallas(q, k, v, causal=True, block_q=bq, block_k=bq,
                               interpret=True)
    b = flash_attention_pallas(q, k, v, causal=True, block_q=128, block_k=128,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

def _ssd_inputs(key, b=2, s=32, nh=4, p=16, g=2, n=8):
    ks = jax.random.split(key, 5)
    xh = jax.random.normal(ks[0], (b, s, nh, p))
    bm = 0.5 * jax.random.normal(ks[1], (b, s, g, n))
    cm = 0.5 * jax.random.normal(ks[2], (b, s, g, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, nh)))
    da = -jnp.exp(jax.random.normal(ks[4], (nh,))) * dt
    dsk = jnp.linspace(0.5, 1.5, nh)
    return xh, bm, cm, dt, da, dsk


@pytest.mark.parametrize("chunk", [8, 16, 32])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_ssd_scan_chunks_groups(chunk, g, rng_key):
    xh, bm, cm, dt, da, dsk = _ssd_inputs(rng_key, g=g)
    y, hf = ssd_scan_pallas(xh, bm, cm, dt, da, dsk, chunk=chunk,
                            interpret=True)
    yr, hr = ref.ssd_scan(xh, bm, cm, dt, da, dsk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr), rtol=1e-4, atol=1e-4)


def test_ssd_scan_bf16_inputs(rng_key):
    xh, bm, cm, dt, da, dsk = _ssd_inputs(rng_key)
    y, _ = ssd_scan_pallas(xh.astype(jnp.bfloat16), bm, cm, dt, da, dsk,
                           chunk=16, interpret=True)
    yr, _ = ref.ssd_scan(xh.astype(jnp.bfloat16), bm, cm, dt, da, dsk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# policy-driven wrapper (offload control)
# ---------------------------------------------------------------------------

def test_ops_threshold_dispatch(rng_key):
    from repro.core.policy import OffloadPolicy, ExecutionMode, Device
    from repro.kernels import ops
    x = jax.random.normal(rng_key, (256, 128))
    small_policy = OffloadPolicy(offload_threshold_bytes=1 << 30)  # never
    y1, _ = ops.offload_copy(x, policy=small_policy, interpret=True)
    y2, _ = ops.offload_copy(
        x, policy=OffloadPolicy(offload_threshold_bytes=1),        # always
        interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6)
