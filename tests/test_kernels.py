"""Pallas kernel validation: interpret-mode execution vs the pure-jnp oracle,
swept over shapes / dtypes / GQA ratios / masking modes (brief deliverable c).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


def _qkv(key, B, Sq, Skv, H, KV, D, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # B, Sq, Skv, H, KV, D, window, block_q, block_k
    (1, 128, 128, 4, 4, 64, 0, 64, 64),        # MHA, square
    (2, 128, 128, 8, 2, 32, 0, 32, 64),        # GQA 4:1
    (2, 64, 256, 4, 4, 64, 0, 64, 64),         # kv longer than q (chunked ctx)
    (1, 256, 256, 6, 2, 128, 0, 128, 128),     # MXU-aligned D
    (2, 128, 128, 4, 1, 64, 0, 64, 32),        # MQA
    (1, 256, 256, 4, 4, 64, 64, 64, 64),       # sliding window
    (1, 192, 192, 4, 2, 64, 32, 64, 64),       # window + ragged tiles
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(case, dtype):
    B, Sq, Skv, H, KV, D, window, bq, bk = case
    q, k, v = _qkv(jax.random.PRNGKey(hash(case) % 2**31), B, Sq, Skv, H, KV,
                   D, dtype)
    lens = jnp.asarray([Skv] + [max(Skv // 2, 1)] * (B - 1), jnp.int32)
    out = flash_attention(q, k, v, lens, causal=True, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, lens, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_flash_jnp():
    """The dry-run jnp path and the kernel agree (same blocking semantics)."""
    from repro.models.attention import flash_prefill
    B, S, H, KV, D = 2, 128, 8, 4, 64
    q, k, v = _qkv(jax.random.PRNGKey(7), B, S, S, H, KV, D, jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    out_jnp = flash_prefill(q, k, v, q_positions=pos, block_k=64)
    out_kernel = flash_attention(q, k, v, block_q=64, block_k=64,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out_jnp), np.asarray(out_kernel),
                               rtol=2e-3, atol=2e-3)


PAGED_CASES = [
    # B, KV, G, D, page, P, nblk
    (2, 2, 4, 64, 16, 16, 4),
    (3, 4, 1, 64, 16, 32, 6),       # MHA-style
    (1, 1, 8, 128, 16, 8, 8),       # MQA, deep table
    (4, 2, 2, 32, 16, 64, 3),
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_vs_ref(case, dtype):
    B, KV, G, D, page, P, nblk = case
    key = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(key, 4)
    H = KV * G
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (P, page, KV, D), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (P, page, KV, D), jnp.float32).astype(dtype)
    tables = jax.random.randint(ks[3], (B, nblk), 0, P)
    lens = jnp.asarray([(nblk * page) - 1] + [page // 2] * (B - 1), jnp.int32)
    out = paged_attention(q, kp, vp, tables, lens, interpret=True)
    ref = paged_attention_ref(q.reshape(B, KV, G, D), kp, vp, tables,
                              lens).reshape(B, H, D)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_matches_dense_decode():
    """Paged kernel == the model's dense ring-buffer decode attention."""
    from repro.models.attention import decode_attention
    B, KV, G, D, page, nblk = 2, 2, 2, 32, 16, 4
    H, S = KV * G, 16 * 4
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kc = jax.random.normal(ks[1], (B, S, KV, D))
    vc = jax.random.normal(ks[2], (B, S, KV, D))
    lens = jnp.asarray([S - 1, 20], jnp.int32)
    dense = decode_attention(q, kc, vc, lens)
    # identity page layout: page i of batch b -> pool page b*nblk+i
    kp = kc.reshape(B * nblk, page, KV, D)
    vp = vc.reshape(B * nblk, page, KV, D)
    tables = jnp.arange(B * nblk, dtype=jnp.int32).reshape(B, nblk)
    paged = paged_attention(q[:, 0], kp, vp, tables, lens,
                            interpret=True).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(paged),
                               rtol=2e-3, atol=2e-3)
