"""jit'd public wrapper: pads the sequence to tile boundaries and lays the
operands out head-major for the kernel. ``interpret`` is the caller's choice:
True runs the kernel in the Pallas interpreter on any backend (the CPU
tests), False compiles it for the TPU."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def flash_attention(q, k, v, lens=None, *, interpret: bool, causal=True,
                    window=0, scale=None, block_q=128, block_k=128):
    """q (B,Sq,H,D); k,v (B,Skv,KV,D); lens (B,) optional valid kv lengths.
    Returns (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    if lens is None:
        lens = jnp.full((B,), Skv, jnp.int32)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    heads_first = (0, 2, 1, 3)
    out = flash_attention_kernel(
        q.transpose(heads_first), k.transpose(heads_first),
        v.transpose(heads_first), lens, causal=causal, window=window,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    out = out.transpose(heads_first)
    return out[:, :Sq] if pad_q else out
