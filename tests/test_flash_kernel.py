"""Pallas fused flash-attention kernel vs the jnp online-softmax oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.attention import flash_attention


def _to_kernel_layout(q, k, v):
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    g = H // KVH
    Sk, Dv = k.shape[1], v.shape[-1]
    qk = q.reshape(B, Sq, KVH, g, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B * KVH, g, Sq, D)
    kk = k.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, D)
    vk = v.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, Dv)
    return qk, kk, vk


def _from_kernel_layout(out, B, KVH, g, Sq, Dv):
    return out.reshape(B, KVH, g, Sq, Dv).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, KVH * g, Dv)


SWEEP = [
    # (B, KVH, g, Sq, Sk, D, Dv, causal, window, blk_q, blk_k, dtype)
    (2, 2, 3, 192, 256, 64, 32, True, None, 64, 64, jnp.float32),
    (2, 2, 3, 192, 256, 64, 32, True, 64, 64, 64, jnp.float32),
    (1, 4, 1, 256, 256, 128, 128, False, None, 128, 128, jnp.float32),
    (1, 1, 8, 100, 130, 32, 32, True, None, 64, 64, jnp.float32),  # ragged
    (2, 2, 2, 128, 128, 64, 64, True, None, 128, 64, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,KVH,g,Sq,Sk,D,Dv,causal,window,bq,bk,dtype", SWEEP)
def test_flash_kernel_matches_oracle(B, KVH, g, Sq, Sk, D, Dv, causal,
                                     window, bq, bk, dtype):
    rng = np.random.default_rng(Sq + Sk)
    q = jnp.asarray(rng.normal(size=(B, Sq, KVH * g, D)), dtype) * 0.3
    k = jnp.asarray(rng.normal(size=(B, Sk, KVH, D)), dtype) * 0.3
    v = jnp.asarray(rng.normal(size=(B, Sk, KVH, Dv)), dtype) * 0.3
    want = flash_attention(q, k, v, causal=causal, window=window,
                           chunk_q=64, chunk_k=64)
    qk, kk, vk = _to_kernel_layout(q, k, v)
    got = flash_attention_pallas(qk, kk, vk, causal=causal, window=window,
                                 blk_q=bq, blk_k=bk, interpret=True)
    got = _from_kernel_layout(got, B, KVH, g, Sq, Dv)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
