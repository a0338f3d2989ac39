"""Pipeline parallelism as a jax-native shard_map schedule.

GPipe-style forward: layers are grouped into `n_stages` stages; stage s lives
on mesh axis "stage" coordinate s. Micro-batches stream through via
lax.ppermute; the schedule runs n_micro + n_stages - 1 ticks and each stage
computes under a validity mask (bubbles execute masked work — the same bubble
fraction (p-1)/(m+p-1) the paper's §II-D/§V-C analyses, here made explicit).

Differentiable end-to-end (grad flows through ppermute), so the same schedule
serves training; tests/test_pipeline.py checks exact equivalence with the
single-device stack.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, params_stacked, x, *, mesh: Mesh,
                     n_micro: int, stage_axis: str = "stage"):
    """x (B, ...) split into n_micro micro-batches along axis 0.

    stage_fn(stage_params, micro_x) -> micro_y, applied by every stage
    (stage_params = params_stacked[s] on stage s).
    params_stacked: pytree with leading axis n_stages.
    Returns y (B, ...) = stage_{p-1}(... stage_0(x)).
    """
    n_stages = mesh.shape[stage_axis]
    B = x.shape[0]
    assert B % n_micro == 0, f"batch {B} % n_micro {n_micro}"
    mb = B // n_micro

    def body(params_local, x_local):
        # params_local: stage slice (leading axis 1); x_local: full batch on
        # stage 0 semantics (we broadcast the input and mask by stage)
        params_here = jax.tree_util.tree_map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(stage_axis)
        micros = x_local.reshape(n_micro, mb, *x_local.shape[1:])
        ticks = n_micro + n_stages - 1
        carry = jnp.zeros_like(stage_fn(params_here, micros[0]))
        outs = jnp.zeros((n_micro, *carry.shape), carry.dtype)

        def tick(t, state):
            carry, outs = state
            # stage 0 ingests micro-batch t (if in range); others take the
            # permuted output of their predecessor
            feed = jnp.where(t < n_micro, micros[jnp.minimum(t, n_micro - 1)],
                             jnp.zeros_like(micros[0]))
            inp = jnp.where(s == 0, feed.astype(carry.dtype), carry)
            out = stage_fn(params_here, inp)
            # valid iff this stage is currently processing micro t-s
            valid = jnp.logical_and(t - s >= 0, t - s < n_micro)
            out = jnp.where(valid, out, jnp.zeros_like(out))
            # last stage records its finished micro-batch
            mi = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = jnp.logical_and(s == n_stages - 1, valid)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, out, outs[mi]), mi, axis=0)
            # hand off to the next stage
            carry = jax.lax.ppermute(
                out, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return carry, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (carry, outs))
        # only the last stage holds real outputs; broadcast them to all
        outs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs.reshape(B, *outs.shape[2:])

    in_specs = (P(stage_axis), P())
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(params_stacked, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
