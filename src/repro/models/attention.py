"""Attention substrate.

``flash_prefill`` — chunked online-softmax attention in pure jnp. This is the
dry-run/roofline path: it never materialises the S x S score matrix (the kv
axis is streamed in ``block_k`` chunks exactly like the Pallas kernel's
BlockSpec loop), so compiled ``memory_analysis()`` stays honest at 32k prefill.
The TPU runtime path is ``repro.kernels.flash_attention`` (same blocking).

``decode_attention`` — one-token attention against a dense ring-buffer cache
(B, S, KV, D) with per-request valid lengths and optional sliding window.

``mla_*`` — Multi-Head Latent Attention (DeepSeek-R1): prefill plus the
*absorbed* decode form whose cache is the (kv_rank + rope) latent per token —
the compression the paper credits for R1's capacity advantage (§V-D).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _chunk_mask(q_pos, kv_pos, kv_limit, window: int):
    """valid[b, q, c]: kv visible to q. q_pos (B,Sq) or (1,Sq); kv_pos (C,);
    kv_limit (B,1) exclusive upper bound on valid cache entries."""
    valid = kv_pos[None, None, :] <= q_pos[..., None]               # causal
    valid &= kv_pos[None, None, :] < kv_limit[..., None]
    if window and window > 0:
        valid &= kv_pos[None, None, :] > q_pos[..., None] - window
    return valid


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  q_positions: jax.Array, kv_lens: Optional[jax.Array] = None,
                  window: int = 0, block_k: int = 512,
                  scale: Optional[float] = None) -> jax.Array:
    """q (B,Sq,H,D); k,v (B,Skv,KV,D); H % KV == 0. Returns (B,Sq,H,D).

    q_positions (B,Sq) or (1,Sq) absolute positions (for chunked prefill the
    offset is the tokens already in cache); kv_lens (B,) exclusive valid length
    of k/v (defaults to Skv).
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, max(Skv, 1))     # never pad beyond the true length
    nchunks = -(-Skv // block_k)
    pad = nchunks * block_k - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if kv_lens is None:
        kv_limit = jnp.full((B, 1), Skv, jnp.int32)
    else:
        kv_limit = kv_lens.astype(jnp.int32).reshape(B, 1)

    qg = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, Sq, KV, g, D)
    qg = jnp.transpose(qg, (0, 2, 3, 1, 4))                         # (B,KV,g,Sq,D)

    def body(carry, ci):
        # named_scope tags these ops in HLO metadata: the roofline analyzer
        # buckets "flash_core" traffic separately because the Pallas runtime
        # kernel keeps scores/stats in VMEM (see analysis/hlo.py SCOPED).
        with jax.named_scope("flash_core"):
            m, l, acc = carry
            start = ci * block_k
            kc = jax.lax.dynamic_slice_in_dim(k, start, block_k, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, block_k, axis=1)
            kc = jnp.transpose(kc, (0, 2, 1, 3))                    # (B,KV,C,D)
            vc = jnp.transpose(vc, (0, 2, 1, 3))
            # bf16 operands, fp32 MXU accumulation — no upcast copies
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qg, kc,
                           preferred_element_type=jnp.float32)
            kv_pos = start + jnp.arange(block_k, dtype=jnp.int32)
            valid = _chunk_mask(q_positions, kv_pos, kv_limit, window)
            s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid[:, None, None, :, :],
                          jnp.exp(s - m_new[..., None]), 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, g, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, g, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, g, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  jnp.arange(nchunks, dtype=jnp.int32))
    out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0.0)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lens: jax.Array, *, window: int = 0,
                     scale: Optional[float] = None,
                     k_new: Optional[jax.Array] = None,
                     v_new: Optional[jax.Array] = None) -> jax.Array:
    """q (B,1,H,D); caches (B,S,KV,D); lens (B,) = index of the newest token
    (attention covers positions 0..lens inclusive). With ``k_new`` and
    ``v_new`` (B,1,KV,D) the newest token is not in the cache: the cache is
    read below ``lens`` and the token is attended beside it, so the caller
    may write it afterwards. Returns (B,1,H,D)."""
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    scale = scale if scale is not None else D ** -0.5
    qg = (q.astype(jnp.float32) * scale).astype(k_cache.dtype).reshape(B, KV, g, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    lens = lens.astype(jnp.int32)
    newest = lens if k_new is None else lens - 1
    valid = pos[None, :] <= newest[:, None]
    if window and window > 0:
        valid &= pos[None, :] > lens[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if k_new is not None:
        s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                           preferred_element_type=jnp.float32)
        m = jnp.maximum(m, s_new)
    p = jnp.exp(s - m[..., None])
    den = jnp.sum(p, axis=-1)
    if k_new is not None:
        p_new = jnp.exp(s_new - m)
        den = den + p_new
    out = jnp.einsum("bkgs,bskd->bkgd",
                     (p / den[..., None]).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    if v_new is not None:
        out = out + jnp.einsum("bkg,bkd->bkgd",
                               (p_new / den).astype(v_new.dtype), v_new[:, 0],
                               preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------- MLA
def mla_prefill(x, p, cfg, positions, kv_lens=None):
    """Multi-Head Latent Attention prefill. Returns (out, (ckv, k_pe)) where the
    returned latents are the decode cache (kv_rank + rope_dim per token)."""
    from repro.models.common import rmsnorm, rope
    ml = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    cq = rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    qs = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"])
    q_nope = qs[..., :ml.qk_nope_head_dim]
    q_pe = rope(qs[..., ml.qk_nope_head_dim:], positions, cfg.rope_theta)
    ckv = rmsnorm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    k_pe = rope((x @ p["w_kr"])[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["w_uk"])
    vv = jnp.einsum("bsr,rhe->bshe", ckv, p["w_uv"])
    scale = (ml.qk_nope_head_dim + ml.qk_rope_head_dim) ** -0.5
    s = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhe,bke->bhqk", q_pe, k_pe)) * scale
    s = s.astype(jnp.float32)
    qp = positions.reshape(1, S) if positions.ndim == 1 else positions
    kpos = jnp.arange(S, dtype=jnp.int32)
    valid = kpos[None, None, :] <= qp[:, :, None]
    if kv_lens is not None:
        valid &= kpos[None, None, :] < kv_lens.astype(jnp.int32)[:, None, None]
    s = jnp.where(valid[:, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqk,bkhe->bqhe", w, vv)
    out = jnp.einsum("bqhe,hed->bqd", ctx, p["w_o"])
    return out, (ckv, k_pe)


def mla_decode(x, p, cfg, ckv_cache, kpe_cache, lens, ckv_new, kpe_new):
    """Absorbed MLA decode: the cache is the latent (B,S,rank)+(B,S,rope),
    read below ``lens``; the newest token's latents ``ckv_new`` (B,1,rank)
    and ``kpe_new`` (B,1,rope) are attended beside it, as
    ``decode_attention`` does with ``k_new``."""
    from repro.models.common import rmsnorm, rope
    ml = cfg.mla
    S = ckv_cache.shape[1]
    pos = lens.astype(jnp.int32)
    cq = rmsnorm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    qs = jnp.einsum("bsr,rhe->bshe", cq, p["w_uq"])
    q_nope = qs[..., :ml.qk_nope_head_dim]
    q_pe = rope(qs[..., ml.qk_nope_head_dim:], pos[:, None], cfg.rope_theta)
    q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, p["w_uk"])          # absorb w_uk
    scale = (ml.qk_nope_head_dim + ml.qk_rope_head_dim) ** -0.5

    def scores(ckv, kpe):                                            # (B,H,T)
        return ((jnp.einsum("bshr,btr->bhst", q_lat, ckv)
                 + jnp.einsum("bshe,bte->bhst", q_pe, kpe)) * scale
                ).astype(jnp.float32)[:, :, 0, :]
    t = jnp.arange(S, dtype=jnp.int32)
    s = jnp.where((t[None, :] < pos[:, None])[:, None, :],
                  scores(ckv_cache, kpe_cache), NEG_INF)
    s = jnp.concatenate([s, scores(ckv_new, kpe_new)], axis=-1)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    ctx_lat = (jnp.einsum("bht,btr->bhr", w[..., :S], ckv_cache)
               + jnp.einsum("bht,btr->bhr", w[..., S:], ckv_new))
    ctx = jnp.einsum("bhr,rhe->bhe", ctx_lat, p["w_uv"])             # absorb w_uv
    out = jnp.einsum("bhe,hed->bd", ctx, p["w_o"])
    return out[:, None, :]
