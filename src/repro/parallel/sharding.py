"""Logical-axis sharding: every parameter/activation carries logical axis
names; a rule table maps them onto mesh axes.

Baseline rules (paper-faithful TP mapping, re-targeted to TPU):
  * weights: FSDP over "data" on the d_model/d_ff contracting axes,
    TP over "model" on heads / mlp / experts / vocab.
  * activations: batch over ("pod","data"); model-axis sharding follows from
    the weights via GSPMD.
  * multi-pod: params replicated across "pod" (gradients all-reduce over pod);
    batch additionally sharded over "pod".

Head padding: TP requires the (q-)head axis divisible by the model-axis size.
``padded_heads`` computes (hp, kvp) such that hp % tp == 0, kvp % tp == 0,
hp % kvp == 0 and (GQA case) kvp % n_kv == 0 — padded q-head slots are
zero-initialised (mathematically inert), replicated kv slots are tiled copies
(exact math; serving-only — the train path shards kv projections on the
contracting axis instead and keeps true kv shapes).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Everything model code needs to know about the device layout."""
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ("data",)   # ("pod","data") for multi-pod
    model_axis: str = "model"
    fsdp_axis: Optional[str] = "data"         # None -> replicate weights over data
    remat: str = "none"                       # none | full
    kv_cache_dtype: Any = None                # default bf16; int8 is a §Perf lever
    moe_dispatch: str = "auto"                # auto | split | replicated
    rules_override: Optional[Dict[str, Any]] = None
    # ---- §Perf hillclimb levers (EXPERIMENTS.md §Perf) ----------------------
    decode_unroll: bool = False     # unrolled decode layers, static cache indices
    serve_2d_tp: bool = False       # contract-dim TP over "data" (no FSDP
                                    # weight gathers in decode; Pope et al.)
    seq_parallel_norm: bool = False  # Megatron-SP residual stream (prefill)
    moe_ff_shard: bool = False      # expert-ffn dim sharded over "data"
                                    # (replaces the expert FSDP gather)
    seq_shard_decode: bool = False  # unpadded kv heads; cache seq over "model"
    train_kv_2d: bool = False       # train kv-proj d_model sharded over BOTH
                                    # axes (partial+psum kills the 16x
                                    # replicated kv compute under TP)

    @property
    def tp(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape[self.model_axis]

    @property
    def dp(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def rules(self) -> Dict[str, Any]:
        r = dict(DEFAULT_RULES)
        r["batch"] = self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        if self.fsdp_axis is None:
            for k in ("embed", "mlp_in", "expert_in"):
                r[k] = None
        else:
            r["embed"] = self.fsdp_axis
        if self.serve_2d_tp:
            r["act_d"] = self.fsdp_axis or "data"
        if self.seq_parallel_norm:
            r["act_seq"] = self.model_axis
        if self.moe_ff_shard:
            r["expert_ff"] = self.fsdp_axis or "data"
        r["embed_kv"] = ((self.fsdp_axis or "data", self.model_axis)
                         if self.train_kv_2d else r["embed"])
        if self.seq_shard_decode:
            r["cache_seq"] = self.model_axis
            r["cache_kv"] = None
        if self.rules_override:
            r.update(self.rules_override)
        return r

    def spec(self, *logical_axes: Optional[str]) -> P:
        rules = self.rules()
        return P(*[rules.get(a) if a is not None else None for a in logical_axes])

    def shard(self, x, *logical_axes):
        """Constrain activation sharding (no-op without a mesh)."""
        if self.mesh is None or self.mesh.size == 1:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(*logical_axes)))


# logical axis -> mesh axis (None = replicated)
DEFAULT_RULES: Dict[str, Any] = {
    "batch": "data",
    "seq": None,
    "embed": "data",        # FSDP: weight d_model axis
    "vocab": "model",       # embedding table vocab axis (TP)
    "heads": "model",       # padded q-head axis
    "kv_heads": "model",    # padded kv-head axis (serve layout)
    "kv_heads_exact": None, # unpadded kv heads (train layout: replicated acts)
    "d_tp": "model",        # untied embedding-table d_model axis (TP)
    "head_dim": None,
    "mlp": "model",         # d_ff axis
    "mlp_in": "data",       # FSDP on the w_down d_ff input axis
    "expert": "model",      # expert-parallel axis
    "expert_in": "data",    # FSDP inside each expert's d_model axis
    "expert_ff": None,      # §Perf moe_ff_shard flips this to "data"
    "layers": None,
    "ssm_inner": "model",
    "ssm_heads": "model",
    "ssm_state": None,
    "conv_ch": "model",
    "lstm_vdim": "model",   # mLSTM value head_dim sharding
    "mla_rank": None,
    "cache_batch": "data",
    "cache_seq": None,      # §Perf flips this to "data"/"model" for seq-sharded KV
    "cache_kv": "model",
    "act_d": None,          # §Perf serve_2d_tp: activation d_model axis
    "act_seq": None,        # §Perf seq_parallel_norm: residual seq axis
    "embed_kv": "data",     # kv-proj d_model axis (train_kv_2d -> 2D tuple)
}

HOST_1D = None  # sentinel for "no mesh"


def single_device_ctx() -> ParallelContext:
    return ParallelContext(mesh=None)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None) -> Mesh:
    """A mesh whose axes are all Auto: the model code relies on GSPMD to
    propagate shardings, which Explicit axes (``jax.make_mesh``'s default)
    would refuse."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_test_mesh(data: int = 1, model: int = 1) -> Mesh:
    return make_mesh((data, model), ("data", "model"))


def padded_heads(n_heads: int, n_kv: int, tp: int) -> Tuple[int, int]:
    """(hp, kvp): padded q/kv head counts for a TP degree (see module doc)."""
    if tp <= 1:
        return n_heads, n_kv
    hp = -(-n_heads // tp) * tp
    if n_kv >= n_heads:                      # MHA: 1:1, zero-pad both
        return hp, hp
    kvp = tp
    while not (hp % kvp == 0 and kvp % n_kv == 0 and kvp >= n_kv):
        kvp += tp
        if kvp > hp:                         # fall back: widen hp to lcm
            hp = abs(hp * n_kv) // math.gcd(hp, n_kv)
            hp = -(-hp // tp) * tp
            kvp = tp
    return hp, kvp


def q_to_orig(hp: int, kvp: int, n_heads: int, n_kv: int) -> np.ndarray:
    """Map padded q slot -> original q head (or -1 for inert pad slots).

    Padded q slots are grouped contiguously by padded kv slot (g' = hp//kvp
    per slot); padded kv slot s replicates original kv head s // (kvp//n_kv)
    (identity + zero-pad in the MHA case). Original q heads of kv group k are
    distributed over that group's replica slots in order.
    """
    out = -np.ones(hp, dtype=np.int64)
    gp = hp // kvp
    if n_kv >= n_heads:                      # MHA identity
        out[:n_heads] = np.arange(n_heads)
        return out
    r = kvp // n_kv
    g = n_heads // n_kv
    for k in range(n_kv):
        orig = list(range(k * g, (k + 1) * g))
        slots = [s * gp + j for s in range(k * r, (k + 1) * r) for j in range(gp)]
        for slot, oq in zip(slots, orig):
            out[slot] = oq
    return out


def kv_to_orig(kvp: int, n_heads: int, n_kv: int) -> np.ndarray:
    """Map padded kv slot -> original kv head (or -1 for zero-pad in MHA)."""
    out = -np.ones(kvp, dtype=np.int64)
    if n_kv >= n_heads:
        out[:n_kv] = np.arange(n_kv)
        return out
    r = kvp // n_kv
    out[:] = np.arange(kvp) // r
    return out
