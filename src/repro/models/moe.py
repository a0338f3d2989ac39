"""Token-choice top-k Mixture-of-Experts with shard_map expert parallelism.

Dispatch is *sort-based* (MegaBlocks-style): assignments are sorted by expert,
positions within each expert come from an exclusive-cumsum histogram, and
tokens are scattered into capacity-bounded (E, C, d) buffers. No (T, E, C)
one-hot tensors exist anywhere, so the dry-run memory analysis stays sane at
kimi-k2 scale (384 experts, 1M batch-tokens).

Two distribution modes (DESIGN.md §5):
  * ``split``      — tokens sharded over the model axis too; all_to_all moves
                     token buffers to their expert-owner shard and back.
                     Used when seq (or batch*seq) divides the model axis
                     (train / prefill).
  * ``replicated`` — tokens replicated over the model axis (decode: one token
                     per sequence); every shard computes its own experts'
                     contribution locally and a psum over the model axis
                     combines. Zero dispatch traffic.

Expert weights are stacked (E, d, f) with E sharded over "model" (EP) and d
over "data" (FSDP); the FSDP gather is an explicit all_gather inside the
shard_map body.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import ParallelContext


def router_probs(x, w_router):
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def _topk_assignments(probs, top_k: int):
    w, idx = jax.lax.top_k(probs, top_k)                    # (T,k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx


def _dispatch_indices(flat_expert: jax.Array, n_experts: int, capacity: int):
    """Sort-based dispatch. flat_expert (A,) -> (slot (A,), keep (A,), order)."""
    A = flat_expert.shape[0]
    order = jnp.argsort(flat_expert, stable=True)
    sorted_e = flat_expert[order]
    counts = jnp.bincount(flat_expert, length=n_experts)
    starts = jnp.cumsum(counts) - counts                    # exclusive cumsum
    pos_in_e = jnp.arange(A, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep_sorted = pos_in_e < capacity
    # dropped assignments get an out-of-range slot so scatter(mode="drop")
    # discards them instead of colliding with a kept token's slot
    slot_sorted = jnp.where(keep_sorted,
                            sorted_e.astype(jnp.int32) * capacity + pos_in_e,
                            n_experts * capacity)
    inv = jnp.argsort(order, stable=True)                   # back to assignment order
    return slot_sorted[inv], keep_sorted[inv]


def _expert_ffn(buf, wg, wu, wd):
    """buf (E, C, d); weights (E, d, f)/(E, f, d)."""
    h = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(h) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_ffn_reference(x, params, cfg) -> jax.Array:
    """Single-device oracle: identical math (incl. capacity drops), no mesh.
    x (T, d) -> (T, d)."""
    m = cfg.moe
    T, d = x.shape
    probs = router_probs(x, params["router"])
    w, idx = _topk_assignments(probs, m.top_k)
    A = T * m.top_k
    capacity = max(1, int(m.capacity_factor * A / m.n_experts))
    flat_e = idx.reshape(A)
    flat_w = w.reshape(A)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), m.top_k)
    slot, keep = _dispatch_indices(flat_e, m.n_experts, capacity)
    buf = jnp.zeros((m.n_experts * capacity, d), x.dtype)
    buf = buf.at[slot].set(x[tok] * keep[:, None].astype(x.dtype), mode="drop")
    out_buf = _expert_ffn(buf.reshape(m.n_experts, capacity, d),
                          params["we_gate"], params["we_up"], params["we_down"])
    gathered = out_buf.reshape(-1, d)[slot]
    contrib = gathered * (flat_w[:, None] * keep[:, None]).astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok].add(contrib)
    if m.n_shared_experts:
        out = out + _shared_ffn(x, params)
    return out


def _shared_ffn(x, params):
    h = jax.nn.silu(x @ params["ws_gate"]) * (x @ params["ws_up"])
    return h @ params["ws_down"]


def moe_ffn(x, params, cfg, ctx: ParallelContext, *, token_axes) -> jax.Array:
    """Distributed MoE FFN. x (..., d) flattened internally to (T, d).

    token_axes: PartitionSpec entry for the token dim of the *flattened* input
    (e.g. ("pod","data")). Chooses split vs replicated dispatch by divisibility.
    """
    if ctx.mesh is None or ctx.mesh.size == 1:
        shape = x.shape
        return moe_ffn_reference(x.reshape(-1, shape[-1]), params, cfg).reshape(shape)

    m = cfg.moe
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    tp = ctx.tp
    dp = ctx.dp
    mode = ctx.moe_dispatch
    if mode == "auto":
        mode = "split" if (T % (dp * tp) == 0 and T // (dp * tp) > 0) else "replicated"

    e_loc = m.n_experts // tp
    mesh = ctx.mesh
    maxis = ctx.model_axis
    faxis = ctx.fsdp_axis

    wspec_in = P(None, faxis, None)     # (E_loc, d/f, f) before gather
    if mode == "split":
        t_loc = T // (dp * tp)
        cap = max(1, int(m.capacity_factor * t_loc * m.top_k / m.n_experts))

        def body(xt_l, router, wg, wu, wd, sg, su, sd):
            # xt_l (t_loc, d) ; router (d, E) ; wg/wu (E_loc, d, f) ; wd (E_loc, f, d)
            if faxis is not None:
                wg = jax.lax.all_gather(wg, faxis, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, faxis, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, faxis, axis=2, tiled=True)
            probs = router_probs(xt_l, router)
            w, idx = _topk_assignments(probs, m.top_k)
            A = t_loc * m.top_k
            flat_e = idx.reshape(A)
            flat_w = w.reshape(A)
            tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), m.top_k)
            slot, keep = _dispatch_indices(flat_e, m.n_experts, cap)
            send = jnp.zeros((m.n_experts * cap, d), xt_l.dtype)
            send = send.at[slot].set(xt_l[tok] * keep[:, None].astype(xt_l.dtype),
                                     mode="drop")
            send = send.reshape(tp, e_loc * cap, d)
            recv = jax.lax.all_to_all(send, maxis, split_axis=0, concat_axis=0,
                                      tiled=False)          # (tp, e_loc*cap, d)
            # recv[p] = tokens from peer p destined to my experts, laid out
            # (e_loc, cap, d). Stack peers on the capacity axis:
            buf = recv.reshape(tp, e_loc, cap, d).transpose(1, 0, 2, 3) \
                      .reshape(e_loc, tp * cap, d)
            out_buf = _expert_ffn(buf, wg, wu, wd)           # (e_loc, tp*cap, d)
            back = out_buf.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
            back = jax.lax.all_to_all(back, maxis, split_axis=0, concat_axis=0,
                                      tiled=False)           # (tp, e_loc, cap, d)
            out_flat = back.reshape(m.n_experts * cap, d)
            gathered = out_flat[slot]
            contrib = gathered * (flat_w[:, None] * keep[:, None]).astype(xt_l.dtype)
            out = jnp.zeros((t_loc, d), xt_l.dtype).at[tok].add(contrib)
            if m.n_shared_experts:
                if faxis is not None:
                    sg = jax.lax.all_gather(sg, faxis, axis=0, tiled=True)
                    su = jax.lax.all_gather(su, faxis, axis=0, tiled=True)
                    sd = jax.lax.all_gather(sd, faxis, axis=1, tiled=True)
                out = out + (jax.nn.silu(xt_l @ sg) * (xt_l @ su)) @ sd
            return out

        tok_spec = P((*(ctx.batch_axes), maxis))
        shared_specs = (P(faxis, None), P(faxis, None), P(None, faxis)) \
            if m.n_shared_experts else (P(), P(), P())
        sh = params.get("ws_gate", jnp.zeros((), x.dtype))
        su_ = params.get("ws_up", jnp.zeros((), x.dtype))
        sd_ = params.get("ws_down", jnp.zeros((), x.dtype))
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P((*(ctx.batch_axes), maxis)), P(None, None),
                      P(maxis, faxis, None), P(maxis, faxis, None),
                      P(maxis, None, faxis), *shared_specs),
            out_specs=tok_spec, check_vma=False,
        )(xt, params["router"], params["we_gate"], params["we_up"],
          params["we_down"], sh, su_, sd_)
        return out.reshape(shape)

    # mode == "replicated": tokens replicated over model axis; each shard runs
    # its local experts on every token, psum combines. (decode path)
    t_loc = T // dp
    cap = max(1, int(m.capacity_factor * t_loc * m.top_k / max(e_loc, 1)))
    ff_shard = ctx.moe_ff_shard and faxis is not None

    def body_rep(xt_l, router, wg, wu, wd, sg, su, sd):
        if faxis is not None and not ff_shard:
            wg = jax.lax.all_gather(wg, faxis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, faxis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, faxis, axis=2, tiled=True)
        probs = router_probs(xt_l, router)
        w, idx = _topk_assignments(probs, m.top_k)
        A = t_loc * m.top_k
        flat_e = idx.reshape(A)
        flat_w = w.reshape(A)
        tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), m.top_k)
        my = jax.lax.axis_index(maxis)
        # keep only assignments owned by this shard's experts
        local = (flat_e >= my * e_loc) & (flat_e < (my + 1) * e_loc)
        local_e = jnp.where(local, flat_e - my * e_loc, 0)
        slot, keep = _dispatch_indices(
            jnp.where(local, local_e, e_loc).astype(jnp.int32), e_loc + 1, cap)
        keep = keep & local
        buf = jnp.zeros(((e_loc + 1) * cap, d), xt_l.dtype)
        buf = buf.at[slot].set(xt_l[tok] * keep[:, None].astype(xt_l.dtype),
                               mode="drop")
        out_buf = _expert_ffn(buf.reshape(e_loc + 1, cap, d)[:e_loc], wg, wu, wd)
        if ff_shard:
            # §Perf: expert d_ff sharded over the fsdp axis — the down-proj
            # is a partial sum; a small activation psum replaces the per-step
            # expert weight all-gather
            out_buf = jax.lax.psum(out_buf, faxis)
        gathered = jnp.concatenate([out_buf.reshape(-1, d),
                                    jnp.zeros((cap, d), xt_l.dtype)])[slot]
        contrib = gathered * (flat_w[:, None] * keep[:, None]).astype(xt_l.dtype)
        out = jnp.zeros((t_loc, d), xt_l.dtype).at[tok].add(contrib)
        out = jax.lax.psum(out, maxis)
        if m.n_shared_experts:
            if ff_shard:
                out = out + jax.lax.psum(
                    (jax.nn.silu(xt_l @ sg) * (xt_l @ su)) @ sd, faxis)
            else:
                if faxis is not None:
                    sg = jax.lax.all_gather(sg, faxis, axis=0, tiled=True)
                    su = jax.lax.all_gather(su, faxis, axis=0, tiled=True)
                    sd = jax.lax.all_gather(sd, faxis, axis=1, tiled=True)
                out = out + (jax.nn.silu(xt_l @ sg) * (xt_l @ su)) @ sd
        return out

    tok_spec = P((*(ctx.batch_axes),))
    if ff_shard:
        wspecs = (P(maxis, None, faxis), P(maxis, None, faxis),
                  P(maxis, faxis, None))
        shared_specs = (P(None, faxis), P(None, faxis), P(faxis, None)) \
            if m.n_shared_experts else (P(), P(), P())
    else:
        wspecs = (P(maxis, faxis, None), P(maxis, faxis, None),
                  P(maxis, None, faxis))
        shared_specs = (P(faxis, None), P(faxis, None), P(None, faxis)) \
            if m.n_shared_experts else (P(), P(), P())
    sh = params.get("ws_gate", jnp.zeros((), x.dtype))
    su_ = params.get("ws_up", jnp.zeros((), x.dtype))
    sd_ = params.get("ws_down", jnp.zeros((), x.dtype))
    out = jax.shard_map(
        body_rep, mesh=mesh,
        in_specs=(tok_spec, P(None, None), *wspecs, *shared_specs),
        out_specs=tok_spec, check_vma=False,
    )(xt, params["router"], params["we_gate"], params["we_up"],
      params["we_down"], sh, su_, sd_)
    return out.reshape(shape)
