"""Flash-attention kernel vs dense oracle: shape/dtype/mask sweeps
(interpret mode on CPU; the kernel targets TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ref import attention_ref


def _mk(key, B, H, KV, Sq, Sk, Dh, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, Sq, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, KV, Sk, Dh), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, KV, Sk, Dh), jnp.float32).astype(dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KV,S,Dh,bq,bk",
    [
        (1, 2, 2, 128, 64, 64, 64),  # MHA
        (2, 4, 2, 128, 64, 64, 32),  # GQA group 2
        (1, 8, 1, 256, 32, 128, 128),  # MQA
        (1, 2, 2, 64, 128, 64, 64),  # single q block
    ],
)
def test_causal_allclose(dtype, B, H, KV, S, Dh, bq, bk):
    q, k, v = _mk(jax.random.PRNGKey(0), B, H, KV, S, S, Dh, dtype)
    got = flash_attention_bhsd(q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), atol=TOL[dtype], rtol=TOL[dtype]
    )


@pytest.mark.parametrize("window", [16, 64, 100])
def test_sliding_window_allclose(window):
    q, k, v = _mk(jax.random.PRNGKey(1), 1, 2, 2, 128, 128, 64, jnp.float32)
    got = flash_attention_bhsd(
        q, k, v, causal=True, window=window, block_q=32, block_k=32, interpret=True
    )
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_bidirectional_allclose():
    q, k, v = _mk(jax.random.PRNGKey(2), 1, 2, 2, 64, 64, 32, jnp.float32)
    got = flash_attention_bhsd(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_k_len_masks_padded_keys():
    q, k, v = _mk(jax.random.PRNGKey(3), 1, 2, 2, 64, 128, 32, jnp.float32)
    got = flash_attention_bhsd(
        q, k, v, causal=False, k_len=100, block_q=32, block_k=32, interpret=True
    )
    want = attention_ref(q, k, v, causal=False, k_len=100)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and the padded tail genuinely doesn't matter
    v2 = v.at[:, :, 100:].set(1e6)
    got2 = flash_attention_bhsd(
        q, k, v2, causal=False, k_len=100, block_q=32, block_k=32, interpret=True
    )
    np.testing.assert_allclose(got2, want, atol=2e-5, rtol=2e-5)


def test_cross_attention_rectangular():
    q, k, v = _mk(jax.random.PRNGKey(4), 2, 4, 4, 64, 192, 32, jnp.float32)
    got = flash_attention_bhsd(q, k, v, causal=False, block_q=32, block_k=64, interpret=True)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_model_layout_wrapper():
    from repro.kernels import ops

    B, S, H, KV, Dh = 2, 128, 4, 2, 64
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(key, (B, S, KV, Dh), jnp.float32)
    v = jax.random.normal(key, (B, S, KV, Dh), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    want = attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), causal=True
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
