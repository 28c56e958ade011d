"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle,
executed in interpret mode (``interpret=True``) on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import flash_attention
from repro.kernels.ref import reference_attention


def make_qkv(key, b, t, s, h, kvh, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    return q, k, v


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,s,h,kvh,d", [
    (1, 128, 128, 2, 2, 64),      # MHA square
    (2, 128, 256, 4, 2, 64),      # GQA, S > T (cache-extended)
    (1, 256, 256, 4, 1, 128),     # MQA, d = 128
    (2, 96, 160, 4, 2, 64),       # non-multiples of block -> padding path
    (1, 8, 8, 2, 2, 32),          # tiny
])
def test_flash_vs_ref_causal(dtype, b, t, s, h, kvh, d):
    q, k, v = make_qkv(0, b, t, s, h, kvh, d, dtype)
    # offset q positions so q attends to the cache prefix (s >= t)
    q_pos = jnp.arange(s - t, s, dtype=jnp.int32)
    out = flash_attention(q, k, v, q_pos=q_pos, block_q=64, block_k=64,
                          interpret=True)
    ref = reference_attention(q, k, v, q_pos=q_pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("window", [1, 7, 64, 1000])
def test_flash_sliding_window(window):
    q, k, v = make_qkv(1, 2, 128, 128, 4, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                          interpret=True)
    ref = reference_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    q, k, v = make_qkv(2, 1, 64, 96, 2, 2, 64, jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_shape():
    """T=1 decode against a long cache."""
    q, k, v = make_qkv(3, 4, 1, 512, 8, 2, 64, jnp.float32)
    q_pos = jnp.asarray([511], jnp.int32)
    out = flash_attention(q, k, v, q_pos=q_pos, block_q=8, block_k=128,
                          interpret=True)
    ref = reference_attention(q, k, v, q_pos=q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_fully_masked_rows_are_zero():
    """Queries with position before every key -> all-masked -> zeros."""
    q, k, v = make_qkv(4, 1, 16, 32, 2, 2, 32, jnp.float32)
    q_pos = jnp.full((16,), -5, jnp.int32)    # before all kv positions
    out = flash_attention(q, k, v, q_pos=q_pos, interpret=True)
    assert np.allclose(np.asarray(out), 0.0)


@settings(max_examples=20, deadline=None)
@given(
    t=st.sampled_from([16, 48, 128]),
    h=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2]),
    d=st.sampled_from([32, 64]),
    window=st.sampled_from([0, 5, 33]),
)
def test_flash_property_sweep(t, h, g, d, window):
    kvh = h
    q, k, v = make_qkv(t * h + d, 1, t, t, h * g, kvh, d, jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=32, block_k=32,
                          interpret=True)
    ref = reference_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def _mlstm_inputs(key, b, t, h, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, h, d), dtype)
    v = jax.random.normal(ks[2], (b, t, h, d), dtype)
    li = jax.random.normal(ks[3], (b, t, h)) * 2
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (b, t, h)) * 2 + 1)
    return q, k, v, li, lf


def test_mlstm_chunkwise_vs_sequential_oracle():
    from repro.models.xlstm import mlstm_chunkwise
    from repro.kernels.ref import reference_mlstm
    q, k, v, li, lf = _mlstm_inputs(7, 2, 128, 2, 32)
    h1, s1 = reference_mlstm(q, k, v, li, lf)
    h2, s2 = mlstm_chunkwise(q, k, v, li, lf, chunk=32)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=5e-4, atol=5e-5)
    for a, b_ in zip(s1, s2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("b,t,h,d,chunk", [
    (1, 64, 1, 16, 16),
    (2, 128, 3, 32, 32),
    (2, 256, 2, 64, 64),     # MXU-aligned head dim
    (1, 96, 2, 32, 48),      # chunk not a power of two
])
def test_mlstm_pallas_kernel_vs_oracle(b, t, h, d, chunk):
    from repro.kernels.ops import mlstm_scan
    from repro.kernels.ref import reference_mlstm
    q, k, v, li, lf = _mlstm_inputs(b * t + d, b, t, h, d)
    out = mlstm_scan(q, k, v, li, lf, chunk=chunk, interpret=True)
    ref, _ = reference_mlstm(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-5)


def test_mlstm_pallas_kernel_bf16():
    from repro.kernels.ops import mlstm_scan
    from repro.kernels.ref import reference_mlstm
    q, k, v, li, lf = _mlstm_inputs(11, 1, 64, 2, 32, jnp.bfloat16)
    out = mlstm_scan(q, k, v, li, lf, chunk=32, interpret=True)
    ref, _ = reference_mlstm(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)
