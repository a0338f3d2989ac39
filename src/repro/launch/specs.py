"""Abstract input specs + shardings for every (arch x shape) dry-run cell.

Everything here is ShapeDtypeStruct-based: the production shapes are never
allocated on this host (the smoke tests exercise reduced configs instead).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ModelConfig
from repro.configs.registry import ShapeSpec
from repro.models import transformer as T
from repro.parallel.sharding import ParallelContext
from repro.train import optimizer as opt_lib


def build_ctx(mesh, multi_pod: bool, cfg: ModelConfig, shape: ShapeSpec,
              opts: Optional[Dict[str, Any]] = None) -> ParallelContext:
    opts = opts or {}
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    dp = int(np.prod([mesh.shape[a] for a in batch_axes]))
    overrides: Dict[str, Any] = {}
    if shape.kind == "decode" and shape.global_batch % dp != 0:
        # long_500k (B=1): batch unshardable -> shard the cache sequence axis
        overrides.update({"batch": None, "cache_batch": None,
                          "cache_seq": "data"})
    overrides.update(opts.get("rules_override", {}))
    kv_dt = opts.get("kv_cache_dtype")
    if isinstance(kv_dt, str):
        kv_dt = {"int8": jnp.int8, "bf16": jnp.bfloat16,
                 "fp8": jnp.float8_e4m3fn}[kv_dt]
    return ParallelContext(
        mesh=mesh,
        batch_axes=batch_axes,
        fsdp_axis=opts.get("fsdp_axis", "data"),
        remat=opts.get("remat", "full" if shape.kind == "train" else "none"),
        kv_cache_dtype=kv_dt,
        moe_dispatch=opts.get("moe_dispatch", "auto"),
        rules_override=overrides or None,
        decode_unroll=bool(opts.get("decode_unroll")),
        serve_2d_tp=bool(opts.get("serve_2d_tp")),
        seq_parallel_norm=bool(opts.get("seq_parallel_norm")),
        moe_ff_shard=bool(opts.get("moe_ff_shard")),
        seq_shard_decode=bool(opts.get("seq_shard_decode")),
        train_kv_2d=bool(opts.get("train_kv_2d")),
    )


def _tok_lens(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[int, int]:
    """(token_len, prefix_len) so prefix+tokens == shape.seq_len."""
    p = cfg.frontend_prefix_len
    return shape.seq_len - p, p


def input_specs(cfg: ModelConfig, shape: ShapeSpec, ctx: ParallelContext,
                act_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Abstract inputs + NamedShardings for the given cell."""
    mesh = ctx.mesh
    B = shape.global_batch
    s_tok, s_pre = _tok_lens(cfg, shape)
    tok_sh = NamedSharding(mesh, ctx.spec("batch", None))

    if shape.kind == "train":
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, s_tok), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, s_tok), jnp.int32),
        }
        shardings = {"tokens": tok_sh, "labels": tok_sh}
        if s_pre:
            batch["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, s_pre, cfg.d_model), act_dtype)
            shardings["prefix_embeds"] = NamedSharding(
                mesh, ctx.spec("batch", None, None))
        return {"batch": batch, "shardings": shardings}

    if shape.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((B, s_tok), jnp.int32)}
        shardings = {"tokens": tok_sh}
        if s_pre:
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, s_pre, cfg.d_model), act_dtype)
            shardings["prefix_embeds"] = NamedSharding(
                mesh, ctx.spec("batch", None, None))
        return {"batch": out, "shardings": shardings}

    # decode: one new token against a seq_len-deep cache
    state = jax.eval_shape(
        lambda: T.init_decode_state(cfg, ctx, B, shape.seq_len,
                                    ctx.kv_cache_dtype or act_dtype))
    return {
        "batch": {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)},
        "shardings": {"tokens": tok_sh},
        "state": state,
        "state_shardings": T.decode_state_shardings(cfg, ctx),
    }
