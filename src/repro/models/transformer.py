"""Composable decoder stack for every assigned architecture family.

Parameter trees are built from ``ParamSpec`` leaves (shape + logical axes +
init), so a single definition yields real initialisation (tests/examples),
abstract ShapeDtypeStructs (dry-run lowering — never allocated), and
NamedShardings (via ParallelContext rules).

Layout modes (DESIGN.md §5):
  * ``train`` — q heads padded to the model-axis multiple and laid out
    *g-major* (reshape (hp,) -> (g, KV) keeps the sharded axis divisible);
    kv projections keep their TRUE head count (replicated over the model
    axis) so tied-replica gradients never diverge.
  * ``serve`` — kv heads tiled to kvp (exact replicas) and laid out
    *kv-major*; the KV cache stores kvp heads sharded over "model".

Homogeneous layer stacks are scanned (single-layer HLO); MoE dense-prefix
layers, zamba2 shared-attention groups and xLSTM 7:1 groups are scanned over
their own homogeneous stacks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import dense_init, rmsnorm, rope, softmax_xent
from repro.parallel.sharding import (ParallelContext, kv_to_orig, padded_heads,
                                     q_to_orig)


# ============================================================== param specs
@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones
    fan_in: int = 1

    def abstract(self, dtype):
        return jax.ShapeDtypeStruct(self.shape, dtype)


def _is_spec(x):
    return isinstance(x, ParamSpec)


def spec_tree_map(f, tree):
    return jax.tree_util.tree_map(f, tree, is_leaf=_is_spec)


def _stackable(spec: ParamSpec, n: int) -> ParamSpec:
    return ParamSpec((n, *spec.shape), ("layers", *spec.axes), spec.init, spec.fan_in)


def heads_layout(cfg: ModelConfig, ctx: ParallelContext, mode: str):
    """Return (hp, kvx) for a mode: serve pads+tiles kv, train keeps true kv
    unless MHA-alignment forces zero-padded kv. With seq-sharded decode
    (§Perf) the serve cache is unpadded too — kv heads replicate and the
    sequence axis carries the model-parallel split instead."""
    tp = ctx.tp
    hp, kvp = padded_heads(cfg.n_heads, cfg.n_kv_heads, tp)
    if mode == "serve":
        if ctx.seq_shard_decode:
            kvt = cfg.n_kv_heads if hp % cfg.n_kv_heads == 0 else kvp
            return hp, kvt
        return hp, kvp
    kvt = cfg.n_kv_heads if hp % cfg.n_kv_heads == 0 else kvp
    return hp, kvt


def _attn_specs(cfg: ModelConfig, ctx: ParallelContext, mode: str) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    if cfg.attention == "mla":
        ml = cfg.mla
        qk = ml.qk_nope_head_dim + ml.qk_rope_head_dim
        s = {
            "w_dq": ParamSpec((d, ml.q_lora_rank), ("embed", None), fan_in=d),
            "q_norm": ParamSpec((ml.q_lora_rank,), (None,), "ones"),
            "w_uq": ParamSpec((ml.q_lora_rank, cfg.n_heads, qk),
                              (None, "heads", None), fan_in=ml.q_lora_rank),
            "w_dkv": ParamSpec((d, ml.kv_lora_rank), ("embed", None), fan_in=d),
            "kv_norm": ParamSpec((ml.kv_lora_rank,), (None,), "ones"),
            "w_kr": ParamSpec((d, ml.qk_rope_head_dim), ("embed", None), fan_in=d),
            "w_uk": ParamSpec((ml.kv_lora_rank, cfg.n_heads, ml.qk_nope_head_dim),
                              (None, "heads", None), fan_in=ml.kv_lora_rank),
            "w_uv": ParamSpec((ml.kv_lora_rank, cfg.n_heads, ml.v_head_dim),
                              (None, "heads", None), fan_in=ml.kv_lora_rank),
            "w_o": ParamSpec((cfg.n_heads, ml.v_head_dim, d),
                             ("heads", None, "embed"), fan_in=cfg.n_heads * ml.v_head_dim),
            "attn_norm": ParamSpec((d,), (None,), "ones"),
        }
        return s
    hp, kvx = heads_layout(cfg, ctx, mode)
    kv_axis = "kv_heads" if (kvx != cfg.n_kv_heads
                             or (mode == "serve" and not ctx.seq_shard_decode)) \
        else "kv_heads_exact"
    # train_kv_2d: unpadded kv projections shard d_model over BOTH mesh axes
    # (2D contracting shard, partial+psum) instead of replicating the kv
    # compute across "model" — a §Perf lever for the train layout
    kv_in = "embed_kv" if (mode == "train" and kv_axis == "kv_heads_exact") \
        else "embed"
    s = {
        "attn_norm": ParamSpec((d,), (None,), "ones"),
        "wq": ParamSpec((d, hp, hd), ("embed", "heads", None), fan_in=d),
        "wk": ParamSpec((d, kvx, hd), (kv_in, kv_axis, None), fan_in=d),
        "wv": ParamSpec((d, kvx, hd), (kv_in, kv_axis, None), fan_in=d),
        "wo": ParamSpec((hp, hd, d), ("heads", None, "embed"), fan_in=hp * hd),
    }
    if cfg.qk_norm:
        s["qn"] = ParamSpec((hd,), (None,), "ones")
        s["kn"] = ParamSpec((hd,), (None,), "ones")
    return s


def _mlp_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "mlp_norm": ParamSpec((d,), (None,), "ones"),
        "w_gate": ParamSpec((d, f), ("embed", "mlp"), fan_in=d),
        "w_up": ParamSpec((d, f), ("embed", "mlp"), fan_in=d),
        "w_down": ParamSpec((f, d), ("mlp", "embed"), fan_in=f),
    }


def _moe_specs(cfg: ModelConfig, ctx: ParallelContext) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    m = cfg.moe
    fe = m.d_ff_expert
    if ctx.moe_ff_shard:
        # §Perf: shard the expert d_ff over the fsdp axis instead of d_model
        # (no per-step expert weight gathers; tiny activation psum instead)
        up_axes = ("expert", None, "expert_ff")
        down_axes = ("expert", "expert_ff", None)
        sg_axes, sd_axes = (None, "expert_ff"), ("expert_ff", None)
    else:
        up_axes = ("expert", "expert_in", None)
        down_axes = ("expert", None, "expert_in")
        sg_axes, sd_axes = ("embed", None), (None, "embed")
    s = {
        "mlp_norm": ParamSpec((d,), (None,), "ones"),
        "router": ParamSpec((d, m.n_experts), (None, None), fan_in=d),
        "we_gate": ParamSpec((m.n_experts, d, fe), up_axes, fan_in=d),
        "we_up": ParamSpec((m.n_experts, d, fe), up_axes, fan_in=d),
        "we_down": ParamSpec((m.n_experts, fe, d), down_axes, fan_in=fe),
    }
    if m.n_shared_experts:
        fs = fe * m.n_shared_experts
        s["ws_gate"] = ParamSpec((d, fs), sg_axes, fan_in=d)
        s["ws_up"] = ParamSpec((d, fs), sg_axes, fan_in=d)
        s["ws_down"] = ParamSpec((fs, d), sd_axes, fan_in=fs)
    return s


def _mamba_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    nh = di // s.head_dim
    ds = s.d_state
    cw = s.conv_width
    ax = ssm_lib.MAMBA_AXES
    shapes = {
        "norm": ((d,), "ones"), "w_z": ((d, di), "normal"), "w_x": ((d, di), "normal"),
        "w_B": ((d, ds), "normal"), "w_C": ((d, ds), "normal"),
        "w_dt": ((d, nh), "normal"),
        "conv_x": ((cw, di), "normal"), "conv_B": ((cw, ds), "normal"),
        "conv_C": ((cw, ds), "normal"),
        "A_log": ((nh,), "zeros"), "D": ((nh,), "ones"), "dt_bias": ((nh,), "zeros"),
        "gnorm": ((di,), "ones"), "out_proj": ((di, d), "normal"),
    }
    fan = {"w_z": d, "w_x": d, "w_B": d, "w_C": d, "w_dt": d,
           "conv_x": cw, "conv_B": cw, "conv_C": cw, "out_proj": di}
    return {k: ParamSpec(sh, ax[k], init, fan.get(k, 1))
            for k, (sh, init) in shapes.items()}


def _mlstm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    di = 2 * d
    nh = cfg.n_heads
    ax = xlstm_lib.MLSTM_AXES
    shapes = {
        "norm": ((d,), "ones"), "w_up": ((d, 2 * di), "normal"),
        "conv": ((4, di), "normal"),
        "w_q": ((di, di), "normal"), "w_k": ((di, di), "normal"),
        "w_v": ((di, di), "normal"), "w_if": ((di, 2 * nh), "normal"),
        "gnorm": ((di,), "ones"), "w_down": ((di, d), "normal"),
        "skip": ((di, di), "normal"),
    }
    fan = {"w_up": d, "conv": 4, "w_q": di, "w_k": di, "w_v": di,
           "w_if": di, "w_down": di, "skip": di}
    return {k: ParamSpec(sh, ax[k], init, fan.get(k, 1))
            for k, (sh, init) in shapes.items()}


def _slstm_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    nh = cfg.n_heads
    hd = d // nh
    ff = int(round(4 * d / 3 / 64)) * 64 or 64
    ax = xlstm_lib.SLSTM_AXES
    shapes = {
        "norm": ((d,), "ones"), "w_gates": ((d, 4 * d), "normal"),
        "r_gates": ((4, nh, hd, hd), "normal"),
        "gnorm": ((d,), "ones"), "w_up": ((d, 2 * ff), "normal"),
        "w_down": ((ff, d), "normal"),
    }
    fan = {"w_gates": d, "r_gates": hd, "w_up": d, "w_down": ff}
    return {k: ParamSpec(sh, ax[k], init, fan.get(k, 1))
            for k, (sh, init) in shapes.items()}


def slstm_ff(cfg: ModelConfig) -> int:
    return int(round(4 * cfg.d_model / 3 / 64)) * 64 or 64


def build_param_specs(cfg: ModelConfig, ctx: ParallelContext, mode: str = "train"):
    d, v = cfg.d_model, cfg.vocab
    tree: Dict[str, Any] = {"final_norm": ParamSpec((d,), (None,), "ones")}
    if cfg.tie_embeddings:
        tree["embed"] = ParamSpec((v, d), ("vocab", None), fan_in=d)
    else:
        tree["embed"] = ParamSpec((v, d), (None, "d_tp"), fan_in=d)
        tree["lm_head"] = ParamSpec((d, v), ("embed", "vocab"), fan_in=d)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        layer = {**_attn_specs(cfg, ctx, mode)}
        if cfg.moe is not None and cfg.moe.n_experts:
            nd = cfg.moe.first_dense_layers
            nm = cfg.n_layers - nd
            moe_layer = {**layer, **_moe_specs(cfg, ctx)}
            tree["moe_stack"] = {k: _stackable(s, nm) for k, s in moe_layer.items()}
            if nd:
                dense_layer = {**layer, **_mlp_specs(cfg)}
                tree["dense_stack"] = {k: _stackable(s, nd) for k, s in dense_layer.items()}
        else:
            dense_layer = {**layer, **_mlp_specs(cfg)}
            tree["dense_stack"] = {k: _stackable(s, cfg.n_layers)
                                   for k, s in dense_layer.items()}
    elif cfg.family == "hybrid":
        tree["mamba_stack"] = {k: _stackable(s, cfg.n_layers)
                               for k, s in _mamba_specs(cfg).items()}
        tree["shared_attn"] = {**_attn_specs(cfg, ctx, mode), **_mlp_specs(cfg)}
    elif cfg.family == "ssm":
        assert cfg.slstm_every > 0
        groups = cfg.n_layers // cfg.slstm_every
        per = cfg.slstm_every - 1
        tree["mlstm_stack"] = {
            k: ParamSpec((groups, per, *s.shape), ("layers", "layers", *s.axes),
                         s.init, s.fan_in)
            for k, s in _mlstm_specs(cfg).items()}
        tree["slstm_stack"] = {k: _stackable(s, groups)
                               for k, s in _slstm_specs(cfg).items()}
    else:
        raise ValueError(cfg.family)
    return tree


def init_params(cfg: ModelConfig, key, ctx: ParallelContext, mode: str = "train",
                dtype=jnp.float32):
    """Random weights from ``key``. One jitted program draws every leaf
    straight into ``dtype`` (no eager float32 copy of a whole stack) and, on
    a mesh, onto its ``param_shardings`` layout; the values do not depend on
    the layout (threefry is partitionable)."""
    specs = build_param_specs(cfg, ctx, mode)
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)

    def make(spec: ParamSpec, k):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        return dense_init(k, spec.shape, max(spec.fan_in, 1), dtype)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        vals = [make(s, k) for s, k in zip(leaves, keys)]
        params = jax.tree_util.tree_unflatten(treedef, vals)
        return _postprocess_init(params, cfg, ctx, mode)

    out = param_shardings(cfg, ctx, mode) if ctx.mesh is not None else None
    return jax.jit(build, out_shardings=out)(key)


def _postprocess_init(params, cfg, ctx, mode):
    """Zero the padded q-head slots (and tile kv replicas in serve mode) so
    padding is mathematically inert."""
    hp, kvx = (None, None)
    if cfg.attention in ("full", "swa") and cfg.family != "ssm":
        hp, kvx = heads_layout(cfg, ctx, mode)
        qmap = _q_slot_to_orig(cfg, ctx, mode)
        kvmap = kv_to_orig(kvx, cfg.n_heads, cfg.n_kv_heads) if kvx != cfg.n_kv_heads \
            else np.arange(kvx)

        def fix_stack(stack):
            if "wq" not in stack:
                return stack
            qmask = jnp.asarray(qmap >= 0, stack["wq"].dtype)
            km = jnp.asarray(np.maximum(kvmap, 0), jnp.int32)
            kmask = jnp.asarray(kvmap >= 0, stack["wk"].dtype)
            out = dict(stack)
            out["wq"] = stack["wq"] * _bmask(qmask, stack["wq"].ndim, -2)
            out["wo"] = stack["wo"] * _bmask(qmask, stack["wo"].ndim, -3)
            if kvx != cfg.n_kv_heads:
                out["wk"] = jnp.take(stack["wk"], km, axis=-2) * _bmask(kmask, stack["wk"].ndim, -2)
                out["wv"] = jnp.take(stack["wv"], km, axis=-2) * _bmask(kmask, stack["wv"].ndim, -2)
            return out

        for name in ("dense_stack", "moe_stack", "shared_attn"):
            if name in params:
                params[name] = fix_stack(params[name])
    return params


def _bmask(mask, ndim, axis):
    """Broadcast a 1-D mask to `ndim` dims placing it at `axis` (negative)."""
    shape = [1] * ndim
    shape[axis] = mask.shape[0]
    return mask.reshape(shape)


def _q_slot_to_orig(cfg, ctx, mode) -> np.ndarray:
    hp, kvx = heads_layout(cfg, ctx, mode)
    if mode == "serve":
        return q_to_orig(hp, kvx, cfg.n_heads, cfg.n_kv_heads)
    # train: g-major layout — slot (j, k) = j*KV + k holds orig head k*g + j
    out = -np.ones(hp, dtype=np.int64)
    g = cfg.n_heads // cfg.n_kv_heads if kvx == cfg.n_kv_heads else 1
    if kvx == cfg.n_kv_heads:
        for k in range(cfg.n_kv_heads):
            for j in range(g):
                out[j * cfg.n_kv_heads + k] = k * g + j
    else:  # MHA zero-padded: identity
        out[:cfg.n_heads] = np.arange(cfg.n_heads)
    return out


def abstract_params(cfg, ctx, mode="train", dtype=jnp.bfloat16):
    specs = build_param_specs(cfg, ctx, mode)
    return spec_tree_map(lambda s: s.abstract(dtype), specs)


def param_shardings(cfg, ctx: ParallelContext, mode="train"):
    specs = build_param_specs(cfg, ctx, mode)
    assert ctx.mesh is not None
    return spec_tree_map(
        lambda s: NamedSharding(ctx.mesh, ctx.spec(*s.axes)), specs)


def param_pspecs(cfg, ctx: ParallelContext, mode="train"):
    specs = build_param_specs(cfg, ctx, mode)
    return spec_tree_map(lambda s: ctx.spec(*s.axes), specs)


# ============================================================== forward
def _gqa_layout(cfg, ctx, mode):
    """(hp, kvx, layout): layout for flash GQA grouping."""
    hp, kvx = heads_layout(cfg, ctx, mode)
    return hp, kvx, ("g_major" if mode == "train" else "kv_major")


def _attn_qkv(x, p, cfg, positions, ctx=None):
    """Project+rope. Returns q (B,S,hp,hd), k,v (B,S,kvx,hd)."""
    h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    if ctx is not None and ctx.serve_2d_tp and h.shape[1] == 1:
        # contract-dim TP (Pope et al. 2D layouts), DECODE-ONLY: the tiny
        # (B,1,d) activation co-shards d with the weights' FSDP shard ->
        # GSPMD emits partial matmul + small psum instead of per-step weight
        # all-gathers. At prefill widths the per-layer activation reshard
        # would dwarf the gathers (measured 5x regression — EXPERIMENTS §Perf).
        h = ctx.shard(h, None, None, "act_d")
    q = jnp.einsum("bsd,dhe->bshe", h, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", h, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", h, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["qn"], cfg.norm_eps)
        k = rmsnorm(k, p["kn"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _flash_gqa(q, k, v, layout, **kw):
    """flash_prefill with either head layout. q (B,S,hp,hd), k (B,S,kvx,hd)."""
    B, S, hp, hd = q.shape
    kvx = k.shape[2]
    if layout == "g_major" and kvx > 1:
        g = hp // kvx
        # (B,S,g,kvx,hd) -> kv-major (B,S,kvx,g,hd) without resharding issues:
        qr = q.reshape(B, S, g, kvx, hd).swapaxes(2, 3).reshape(B, S, hp, hd)
        out = attn.flash_prefill(qr, k, v, **kw)
        return out.reshape(B, S, kvx, g, hd).swapaxes(2, 3).reshape(B, S, hp, hd)
    return attn.flash_prefill(q, k, v, **kw)


def _decode_gqa(q, kc, vc, lens, layout, **kw):
    B, _, hp, hd = q.shape
    kvx = kc.shape[2]
    if layout == "g_major" and kvx > 1:
        g = hp // kvx
        qr = q.reshape(B, 1, g, kvx, hd).swapaxes(2, 3).reshape(B, 1, hp, hd)
        out = attn.decode_attention(qr, kc, vc, lens, **kw)
        return out.reshape(B, 1, kvx, g, hd).swapaxes(2, 3).reshape(B, 1, hp, hd)
    return attn.decode_attention(q, kc, vc, lens, **kw)


def _mlp(x, p, cfg, ctx, token_axes=None):
    h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    if "router" in p:
        return moe_lib.moe_ffn(h, p, cfg, ctx, token_axes=token_axes)
    if ctx.serve_2d_tp and h.shape[1] == 1:
        h = ctx.shard(h, None, None, "act_d")
    g = jnp.einsum("bsd,df->bsf", h, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", h, p["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"])


def _attn_mlp_layer_fwd(x, p, cfg, ctx, positions, mode, *, window,
                        return_kv=False):
    _, _, layout = _gqa_layout(cfg, ctx, mode)
    if cfg.attention == "mla":
        y, latents = attn.mla_prefill(
            rmsnorm(x, p["attn_norm"], cfg.norm_eps), p, cfg, positions)
        x = x + y
        x = x + _mlp(x, p, cfg, ctx)
        return (x, latents) if return_kv else (x, None)
    if ctx.seq_parallel_norm:
        # Megatron-SP: the residual stream lives seq-sharded on the model
        # axis; GSPMD turns the per-block all-reduces into RS+AG pairs
        # (half the wire bytes)
        x = ctx.shard(x, "batch", "act_seq", None)
    q, k, v = _attn_qkv(x, p, cfg, positions, ctx)
    qp = positions if positions.ndim == 2 else positions[None, :]
    o = _flash_gqa(q, k, v, layout, q_positions=qp, window=window)
    x = x + jnp.einsum("bshe,hed->bsd", o, p["wo"])
    x = x + _mlp(x, p, cfg, ctx)
    return (x, (k, v)) if return_kv else (x, None)


_WRITE_BLOCK = 128      # positions per cache write: one lane tile on a TPU


def _attn_mlp_layer_decode(x, p, cfg, ctx, cache, l, lens, *, window):
    """Layer ``l`` of a stack whose cache (dict of stacked (L,B,S,...) k/v or
    MLA latents) is read in place: attention takes the cached positions
    below ``lens`` and the new token beside them. Returns x and the new
    token's cache entries (B,1,...) in the cache's dtype, which
    ``_write_tokens`` stores after the layer loop."""
    _, _, layout = _gqa_layout(cfg, ctx, "serve")
    if cfg.attention == "mla":
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        ckv = rmsnorm(h @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
        kpe = rope((h @ p["w_kr"])[:, :, None, :], lens[:, None], cfg.rope_theta)[:, :, 0]
        new = {"ckv": ckv.astype(cache["ckv"].dtype),
               "kpe": kpe.astype(cache["kpe"].dtype)}
        y = attn.mla_decode(h, p, cfg, cache["ckv"][l], cache["kpe"][l], lens,
                            new["ckv"], new["kpe"])
        x = x + y
        x = x + _mlp(x, p, cfg, ctx)
        return x, new
    q, k, v = _attn_qkv(x, p, cfg, lens[:, None], ctx)
    new = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
    o = _decode_gqa(q, cache["k"][l], cache["v"][l], lens, layout,
                    window=window, k_new=new["k"], v_new=new["v"])
    x = x + jnp.einsum("bshe,hed->bsd", o, p["wo"])
    x = x + _mlp(x, p, cfg, ctx)
    return x, new


def _write_tokens(cache, new, lens, active):
    """Write each active slot's new token of every layer, ``new`` (L,B,1,...),
    into the stacked cache (L,B,S,...) at its position ``lens``, in place.

    Each slot reads the aligned block of ``_WRITE_BLOCK`` positions that
    holds ``lens`` and writes it back with its tokens merged in where the
    slot is active, so an inactive slot's bytes come back unchanged (a slot
    at ``lens == S`` merges nothing). One dynamic update per slot and step
    leaves the cache in the layout the attention reads: on a v5e with
    120-wide heads the sequence is the minor axis, and a scatter, whose
    window must be minor, makes the compiler copy the whole cache into
    another layout and back."""
    (L, B, S), rest = cache.shape[:3], cache.shape[3:]
    w = min(_WRITE_BLOCK, S)
    start = jnp.minimum(jax.lax.div(lens, w) * w, S - w)
    hit = (jnp.arange(w) == (lens - start)[:, None]) & active[:, None]
    hit = hit.reshape((B, 1, 1, w) + (1,) * len(rest))
    tail = (0,) * len(rest)
    for b in range(B):
        at = (0, b, start[b]) + tail
        block = jax.lax.dynamic_slice(cache, at, (L, 1, w) + rest)
        block = jnp.where(hit[b], new[:, b:b + 1], block)
        cache = jax.lax.dynamic_update_slice(cache, block, at)
    return cache


def _decode_unrolled_stack(x, stack_params, cache, cfg, ctx, lens, window):
    """Unrolled decode over a homogeneous stack with stacked caches
    (L,B,S,kv,hd): per-layer params/cache use *static* indices, and
    attention dots read the cache slice directly (no materialised per-layer
    copies). Returns x and the new tokens, stacked as ``lax.scan`` would."""
    L = cache["k"].shape[0]
    news = []
    for l in range(L):
        p = jax.tree_util.tree_map(lambda a: a[l], stack_params)
        x, new = _attn_mlp_layer_decode(x, p, cfg, ctx, cache, l, lens,
                                        window=window)
        news.append(new)
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *news)


def _maybe_remat(fn, ctx):
    if ctx.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


# --------------------------------------------------------- full-sequence fwd
def forward(params, tokens, cfg: ModelConfig, ctx: ParallelContext, *,
            mode: str = "train", prefix_embeds=None, return_caches: bool = False):
    """tokens (B,S_tok) int32; prefix_embeds (B,P,d) for vlm/audio.
    Returns (logits (B,S,V), caches-or-None)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    x = ctx.shard(x, "batch", None, None)
    positions = jnp.arange(S, dtype=jnp.int32)
    window = cfg.swa_window if cfg.attention == "swa" else 0
    caches = {}

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def body(x, p):
            return _attn_mlp_layer_fwd(x, p, cfg, ctx, positions, mode,
                                       window=window, return_kv=return_caches)
        body = _maybe_remat(body, ctx)
        for name in ("dense_stack", "moe_stack"):
            if name in params:
                x, kv = jax.lax.scan(body, x, params[name])
                if return_caches:
                    caches[name] = kv
    elif cfg.family == "hybrid":
        x, caches = _hybrid_forward(x, params, cfg, ctx, positions, mode,
                                    return_caches)
    elif cfg.family == "ssm":
        x, caches = _xlstm_forward(x, params, cfg, ctx, return_caches)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = ctx.shard(logits, "batch", None, "vocab")
    return logits, (caches if return_caches else None)


def _hybrid_forward(x, params, cfg, ctx, positions, mode, return_caches):
    groups = cfg.n_layers // cfg.attn_every
    per = cfg.attn_every
    window = 0
    shared = params["shared_attn"]
    mstack = jax.tree_util.tree_map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), params["mamba_stack"])

    def mamba_body(x, p):
        y, _ = ssm_lib.mamba2_forward(x, p, cfg)
        return x + y, None

    def group_body(x, pg):
        x, kv = _attn_mlp_layer_fwd(x, shared, cfg, ctx, positions, mode,
                                    window=window, return_kv=return_caches)
        x, _ = jax.lax.scan(mamba_body, x, pg)
        return x, kv

    x, kvs = jax.lax.scan(group_body, x, mstack)
    return x, ({"shared_attn": kvs} if return_caches else {})


def _xlstm_forward(x, params, cfg, ctx, return_caches):
    def group_body(x, pg):
        pm, ps = pg

        def m_body(x, p):
            y, st = xlstm_lib.mlstm_forward(x, p, cfg)
            return y, (st if return_caches else None)
        x, mst = jax.lax.scan(m_body, x, pm)
        x, sst = xlstm_lib.slstm_forward(x, ps, cfg)
        return x, ((mst, sst) if return_caches else None)

    x, states = jax.lax.scan(group_body, x,
                             (params["mlstm_stack"], params["slstm_stack"]))
    return x, ({"xlstm": states} if return_caches else {})


def loss_fn(params, batch, cfg: ModelConfig, ctx: ParallelContext):
    logits, _ = forward(params, batch["tokens"], cfg, ctx, mode="train",
                        prefix_embeds=batch.get("prefix_embeds"))
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:   # vlm/audio prefix: no loss on prefix
        pad = logits.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.zeros((labels.shape[0], pad), labels.dtype), labels], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((labels.shape[0], pad), jnp.float32),
             jnp.ones((labels.shape[0], labels.shape[1] - pad), jnp.float32)],
            axis=1)
    else:
        mask = batch.get("mask")
    return softmax_xent(logits, labels, mask)


# --------------------------------------------------------------- serve paths
def _cache_stacks(cfg: ModelConfig):
    """(name, layers) of each attention stack that keeps a decode cache."""
    if cfg.moe and cfg.moe.n_experts:
        nd = cfg.moe.first_dense_layers
        stacks = (("dense_stack", nd), ("moe_stack", cfg.n_layers - nd))
    else:
        stacks = (("dense_stack", cfg.n_layers),)
    return [(name, n) for name, n in stacks if n]


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def decode_state_axes(cfg: ModelConfig):
    """Logical axes of every leaf of ``init_decode_state``'s tree, in the
    same structure. "cache_batch" is each leaf's slot axis."""
    axes: Dict[str, Any] = {"lens": ("cache_batch",)}
    kv = ("layers", "cache_batch", "cache_seq", "cache_kv", None)
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        lat = ("layers", "cache_batch", "cache_seq", None)
        leaf = {"ckv": lat, "kpe": lat} if cfg.attention == "mla" \
            else {"k": kv, "v": kv}
        axes["caches"] = {name: leaf for name, _ in _cache_stacks(cfg)}
    elif cfg.family == "hybrid":
        axes["caches"] = {"shared_attn": {"k": kv, "v": kv}}
        bc = ("layers", "cache_batch", None, None)
        axes["mamba"] = (("layers", "cache_batch", "ssm_heads", None, None),
                         (("layers", "cache_batch", None, "ssm_inner"), bc, bc))
    elif cfg.family == "ssm":
        def m(*rest):
            return ("layers", "layers", "cache_batch", *rest)
        axes["mlstm"] = (m(None, None, None), m(None, None), m(None),
                         m(None, None))
        s = ("layers", "cache_batch", None)
        axes["slstm"] = (s, s, s, s)
    return axes


def decode_slot_axes(cfg: ModelConfig):
    """The slot (batch) axis of every decode-state leaf, as an int tree."""
    return jax.tree_util.tree_map(lambda a: a.index("cache_batch"),
                                  decode_state_axes(cfg), is_leaf=_is_axes)


def decode_state_shardings(cfg: ModelConfig, ctx: ParallelContext):
    return jax.tree_util.tree_map(
        lambda a: NamedSharding(ctx.mesh, ctx.spec(*a)),
        decode_state_axes(cfg), is_leaf=_is_axes)


def init_decode_state(cfg: ModelConfig, ctx: ParallelContext, batch: int,
                      max_len: int, dtype=jnp.bfloat16):
    """Allocate the decode cache pytree (dense ring-buffer layout); its
    leaves' logical axes are ``decode_state_axes``."""
    hd = cfg.resolved_head_dim
    hp, kvp = heads_layout(cfg, ctx, "serve")
    state: Dict[str, Any] = {"lens": jnp.zeros((batch,), jnp.int32)}
    cdt = ctx.kv_cache_dtype or dtype
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        caches = {}
        for name, n in _cache_stacks(cfg):
            if cfg.attention == "mla":
                ml = cfg.mla
                caches[name] = {
                    "ckv": jnp.zeros((n, batch, max_len, ml.kv_lora_rank), cdt),
                    "kpe": jnp.zeros((n, batch, max_len, ml.qk_rope_head_dim), cdt),
                }
            else:
                caches[name] = {
                    "k": jnp.zeros((n, batch, max_len, kvp, hd), cdt),
                    "v": jnp.zeros((n, batch, max_len, kvp, hd), cdt),
                }
        state["caches"] = caches
    elif cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.attn_every
        state["caches"] = {"shared_attn": {
            "k": jnp.zeros((groups, batch, max_len, kvp, hd), cdt),
            "v": jnp.zeros((groups, batch, max_len, kvp, hd), cdt)}}
        h, cs = ssm_lib.init_mamba_state(cfg, batch, cdt)
        state["mamba"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers, *a.shape)).copy(),
            (h, cs))
    elif cfg.family == "ssm":
        groups = cfg.n_layers // cfg.slstm_every
        per = cfg.slstm_every - 1
        mst = xlstm_lib.init_mlstm_state(cfg, batch, cdt)
        state["mlstm"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (groups, per, *a.shape)).copy(), mst)
        sst = xlstm_lib.init_slstm_state(cfg, batch)
        state["slstm"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (groups, *a.shape)).copy(), sst)
    return state


def decode_step(params, state, tokens, cfg: ModelConfig, ctx: ParallelContext,
                active=None):
    """One decode step for the whole batch. tokens (B,1) -> logits (B,1,V).

    ``active`` (B,) bool marks the slots to advance, every slot when None.
    An active slot's new keys and values are written in place at its
    ``lens``, which then advances; an inactive slot's cache comes back bit
    for bit and its length stays, because no token is merged into it
    (``_write_tokens``). The recurrent states (mamba, xLSTM) are recomputed
    for every slot: a caller that pauses slots keeps their old values
    itself (``JaxRunner``)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    lens = state["lens"]
    if active is None:
        active = jnp.ones(lens.shape, bool)
    window = cfg.swa_window if cfg.attention == "swa" else 0
    new_state = dict(state)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        caches = state["caches"]
        new_caches = {}
        for name, n in _cache_stacks(cfg):
            if ctx.decode_unroll and cfg.attention != "mla":
                # §Perf: unrolled layers + static cache indexing
                x, new = _decode_unrolled_stack(x, params[name], caches[name],
                                                cfg, ctx, lens, window)
            else:
                # the cache is neither the loop's xs nor its ys: each layer
                # reads its own slice of it, and its new token comes out
                def body(x, pl):
                    p, l = pl
                    return _attn_mlp_layer_decode(x, p, cfg, ctx, caches[name],
                                                  l, lens, window=window)
                x, new = jax.lax.scan(
                    body, x, (params[name], jnp.arange(n, dtype=jnp.int32)))
            new_caches[name] = {k: _write_tokens(c, new[k], lens, active)
                                for k, c in caches[name].items()}
        new_state["caches"] = new_caches
    elif cfg.family == "hybrid":
        x, new_state = _hybrid_decode(x, params, state, cfg, ctx, lens,
                                      active)
    elif cfg.family == "ssm":
        x, new_state = _xlstm_decode(x, params, state, cfg, ctx)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    new_state["lens"] = lens + active.astype(lens.dtype)
    return logits, new_state


def _hybrid_decode(x, params, state, cfg, ctx, lens, active):
    groups = cfg.n_layers // cfg.attn_every
    per = cfg.attn_every
    shared = params["shared_attn"]
    mstack = jax.tree_util.tree_map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), params["mamba_stack"])
    mstate = jax.tree_util.tree_map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), state["mamba"])
    cache = state["caches"]["shared_attn"]

    def group_body(x, inp):
        pg, mst_g, g = inp
        x, new = _attn_mlp_layer_decode(x, shared, cfg, ctx, cache, g, lens,
                                        window=0)

        def m_body(x, pm_st):
            pm, st = pm_st
            y, nst = ssm_lib.mamba2_decode(x, pm, cfg, st)
            return x + y, nst
        x, nms = jax.lax.scan(m_body, x, (pg, mst_g))
        return x, (new, nms)

    x, (new, nmamba) = jax.lax.scan(
        group_body, x, (mstack, mstate, jnp.arange(groups, dtype=jnp.int32)))
    new_state = dict(state)
    new_state["caches"] = {"shared_attn": {
        k: _write_tokens(c, new[k], lens, active) for k, c in cache.items()}}
    new_state["mamba"] = jax.tree_util.tree_map(
        lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), nmamba)
    return x, new_state


def _xlstm_decode(x, params, state, cfg, ctx):
    def group_body(x, inp):
        pm, ps, mst, sst = inp

        def m_body(x, pst):
            p, st = pst
            y, nst = xlstm_lib.mlstm_decode(x, p, cfg, st)
            return y, nst
        x, nmst = jax.lax.scan(m_body, x, (pm, mst))
        x, nsst = xlstm_lib.slstm_forward(x, ps, cfg, initial_state=sst)
        return x, (nmst, nsst)

    x, (nm, ns) = jax.lax.scan(
        group_body, x,
        (params["mlstm_stack"], params["slstm_stack"],
         state["mlstm"], state["slstm"]))
    new_state = dict(state)
    new_state["mlstm"] = nm
    new_state["slstm"] = ns
    return x, new_state


def prefill(params, tokens, cfg: ModelConfig, ctx: ParallelContext, *,
            prefix_embeds=None, max_len: Optional[int] = None,
            prompt_lens=None, cache_dtype=jnp.bfloat16):
    """Run the prompt, build a decode state. tokens (B,S). Returns
    (last-token logits (B,V), DecodeState)."""
    B, S = tokens.shape[0], tokens.shape[1]
    if prefix_embeds is not None:
        S = S + prefix_embeds.shape[1]
    max_len = max_len or S
    logits, caches = forward(params, tokens, cfg, ctx, mode="serve",
                             prefix_embeds=prefix_embeds, return_caches=True)
    if prompt_lens is None:
        prompt_lens = jnp.full((B,), S, jnp.int32)
    state = init_decode_state(cfg, ctx, B, max_len, cache_dtype)
    state["lens"] = prompt_lens.astype(jnp.int32)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        for name, kv in caches.items():
            tgt = state["caches"][name]
            if cfg.attention == "mla":
                ckv, kpe = kv
                tgt["ckv"] = _fill(tgt["ckv"], ckv.astype(tgt["ckv"].dtype))
                tgt["kpe"] = _fill(tgt["kpe"], kpe.astype(tgt["kpe"].dtype))
            else:
                k, v = kv
                tgt["k"] = _fill(tgt["k"], k.astype(tgt["k"].dtype))
                tgt["v"] = _fill(tgt["v"], v.astype(tgt["v"].dtype))
    elif cfg.family == "hybrid":
        k, v = caches["shared_attn"]
        tgt = state["caches"]["shared_attn"]
        tgt["k"] = _fill(tgt["k"], k.astype(tgt["k"].dtype))
        tgt["v"] = _fill(tgt["v"], v.astype(tgt["v"].dtype))
        # re-run mamba to harvest final states (cheap at small scale; the
        # engine path uses run_prefill_with_state below)
        state["mamba"] = _harvest_mamba_states(params, tokens, cfg, ctx,
                                               prefix_embeds)
    elif cfg.family == "ssm":
        mst, sst = _harvest_xlstm_states(params, tokens, cfg, ctx)
        state["mlstm"], state["slstm"] = mst, sst
    last = jnp.take_along_axis(
        logits, (state["lens"][:, None, None] - 1).astype(jnp.int32), axis=1)[:, 0]
    return last, state


def _fill(cache, kv):
    """cache (L,B,Smax,...); kv (L,B,S,...) -> write prefix."""
    return cache.at[:, :, :kv.shape[2]].set(kv)


def _harvest_mamba_states(params, tokens, cfg, ctx, prefix_embeds):
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    groups = cfg.n_layers // cfg.attn_every
    per = cfg.attn_every
    shared = params["shared_attn"]
    mstack = jax.tree_util.tree_map(
        lambda a: a.reshape(groups, per, *a.shape[1:]), params["mamba_stack"])

    def mamba_body(x, p):
        y, st = ssm_lib.mamba2_forward(x, p, cfg)
        return x + y, st

    def group_body(x, pg):
        x, _ = _attn_mlp_layer_fwd(x, shared, cfg, ctx, positions, "serve",
                                   window=0, return_kv=False)
        x, sts = jax.lax.scan(mamba_body, x, pg)
        return x, sts

    _, sts = jax.lax.scan(group_body, x, mstack)
    return jax.tree_util.tree_map(
        lambda a: a.reshape(cfg.n_layers, *a.shape[2:]), sts)


def _harvest_xlstm_states(params, tokens, cfg, ctx):
    x = jnp.take(params["embed"], tokens, axis=0)

    def group_body(x, pg):
        pm, ps = pg

        def m_body(x, p):
            y, st = xlstm_lib.mlstm_forward(x, p, cfg)
            return y, st
        x, mst = jax.lax.scan(m_body, x, pm)
        x, sst = xlstm_lib.slstm_forward(x, ps, cfg)
        return x, (mst, sst)

    _, (mst, sst) = jax.lax.scan(group_body, x,
                                 (params["mlstm_stack"], params["slstm_stack"]))
    return mst, sst
