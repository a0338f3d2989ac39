"""Pallas TPU flash-attention (prefill) kernel.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the kv-block axis is the
innermost (sequential) dimension; online-softmax stats (m, l) and the output
accumulator live in VMEM scratch and persist across kv-block steps.

Head-major layout, so the last two dims of every block are (rows, D) and
satisfy the TPU tiling rule for any head_dim (D is the full last dim):
  q   (1, 1, block_q, D)   revisited for every kv block
  k/v (1, 1, block_k, D)   kv head = q_head // group
  out (1, 1, block_q, D)   written once, on the last kv block

Causal + sliding-window masking is applied inside the kernel from the global
block offsets; kv blocks strictly above the diagonal (or outside the window)
are skipped with pl.when so the MXU work is elided, not just masked.
VMEM budget per grid cell: q/k/v tiles 3x32KB + scores 64KB + acc 64KB (fp32)
~= 0.2 MB, far under the ~16 MB/core budget -> Pallas double-buffers freely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int,
                  causal: bool, window: int, seq_kv: int):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # skip fully-masked kv blocks (strictly above the causal diagonal, or
    # entirely left of the sliding window)
    run = jnp.bool_(True)
    if causal:
        run = k_start <= q_start + block_q - 1
    if window and window > 0:
        run = jnp.logical_and(run, k_start + block_k - 1
                              > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q.astype(k.dtype), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        valid = k_pos < lens_ref[b]
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        if window and window > 0:
            valid = jnp.logical_and(valid, k_pos > q_pos - window)
        s = jnp.where(valid, s, NEG_INF)

        # stats are (block_q, 1) columns: TPU vectors are 2-D
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit re-mask: fully-masked rows would otherwise get exp(0)=1
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_kernel(q, k, v, lens, *, causal=True, window=0,
                           scale=None, block_q=128, block_k=128,
                           interpret=False):
    """q (B,H,Sq,D); k,v (B,KV,Skv,D); lens (B,) int32 valid kv length.
    Returns (B,H,Sq,D). H % KV == 0 (GQA via kv-head revisiting)."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    g = H // KV
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, \
        f"seq ({Sq},{Skv}) must tile by ({block_q},{block_k})"
    grid = (B, H, Sq // block_q, Skv // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_kv=Skv)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps receive the scalar-prefetch ref as a trailing arg
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, iq, ik, lens: (b, h, iq, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, iq, ik, lens: (b, h // g, ik, 0)),
                pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, iq, ik, lens: (b, h // g, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, D),
                                   lambda b, h, iq, ik, lens: (b, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), q, k, v)
