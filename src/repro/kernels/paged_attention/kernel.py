"""Pallas TPU paged-attention decode kernel.

One new token per sequence attends over a block-table-indexed paged KV cache
(vLLM layout, page = 16 tokens, DESIGN.md §2 hardware adaptation: the CUDA
warp-reduction kernel becomes a VMEM-blocked online-softmax loop; pages are
DMA'd HBM->VMEM by the BlockSpec index_map driven from the scalar-prefetched
block table).

Grid: (batch, kv_heads, num_pages) — pages innermost/sequential; the q-group
accumulator (g, D) and stats live in VMEM scratch across page steps.

The pool is head-major (KV, P, page, D), so the last two dims of every block
are (rows, D) and satisfy the TPU tiling rule for any head_dim:
  q        (1, 1, G, D)     revisited per page
  k/v page (1, 1, page, D)  page id = block_table[b, j]
  out      (1, 1, G, D)     written on the last page

Pages past ceil(len/page) are skipped with pl.when (DMA still issued for the
block — acceptable at page granularity; a fully dynamic grid would need
ragged iteration, noted as a TPU-side future optimisation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    npg = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b] + 1          # cache holds positions 0..len inclusive
    n_used = (seq_len + page - 1) // page

    @pl.when(j < n_used)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, D)
        k = k_ref[0, 0]                                       # (page, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q.astype(k.dtype), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,page)
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = pos < seq_len
        s = jnp.where(valid, s, NEG_INF)
        # stats are (G, 1) columns: TPU vectors are 2-D
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == npg - 1)
    def _finish():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret"))
def paged_attention_kernel(q, k_pages, v_pages, block_tables, lens, *,
                           scale=None, interpret=False):
    """q (B,KV,G,D); k/v_pages (KV, P, page, D); block_tables (B, max_blocks)
    int32 page ids; lens (B,) index of the newest token. Returns (B,KV,G,D)."""
    B, KV, G, D = q.shape
    page = k_pages.shape[2]
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    grid = (B, KV, max_blocks)

    kernel = functools.partial(_paged_kernel, page=page, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,     # block_tables, lens
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D),
                             lambda b, h, j, tables, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, page, D),
                             lambda b, h, j, tables, lens:
                             (h, tables[b, j], 0, 0)),
                pl.BlockSpec((1, 1, page, D),
                             lambda b, h, j, tables, lens:
                             (h, tables[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, tables, lens: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32), q,
      k_pages, v_pages)
