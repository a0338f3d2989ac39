"""Compile the serving path's kernels and steps for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described and not attached, and refuses what the chip would
refuse (a block that breaks the tiling rule, a program that does not fit).
The Pallas interpreter, which the other kernel tests use, checks neither.
The topology is described inside a fixture, never at import, so collecting
this file loads no TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import transformer as T
from repro.parallel.sharding import single_device_ctx

V5E_HBM_BYTES = 16 * 2**30
DANUBE = get_config("h2o-danube-3-4b")
H, KV = DANUBE.n_heads, DANUBE.n_kv_heads          # 32 q heads, 8 kv heads: G=4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("head_dim", [DANUBE.resolved_head_dim, 128])
def test_flash_attention_compiles(one_chip, head_dim):
    S = 512
    fn = jax.jit(lambda q, k, v, lens: flash_attention(
        q, k, v, lens, interpret=False, window=DANUBE.swa_window))
    compiled = fn.lower(_spec(one_chip, (1, S, H, head_dim)),
                        _spec(one_chip, (1, S, KV, head_dim)),
                        _spec(one_chip, (1, S, KV, head_dim)),
                        _spec(one_chip, (1,), jnp.int32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("head_dim", [DANUBE.resolved_head_dim, 128])
def test_paged_attention_compiles(one_chip, head_dim):
    B, pool, page, blocks = 8, 64, 16, 8
    fn = jax.jit(lambda q, kp, vp, tables, lens: paged_attention(
        q, kp, vp, tables, lens, interpret=False))
    compiled = fn.lower(_spec(one_chip, (B, H, head_dim)),
                        _spec(one_chip, (pool, page, KV, head_dim)),
                        _spec(one_chip, (pool, page, KV, head_dim)),
                        _spec(one_chip, (B, blocks), jnp.int32),
                        _spec(one_chip, (B,), jnp.int32)).compile()
    _assert_kernel(compiled)


def test_danube_decode_step_compiles(one_chip):
    """The decode step at published widths, depth cut to 2 layers, with the
    serving cache (8 slots of 1024 positions, bf16) donated and written in
    place: its temporaries hold no copy of the cache."""
    import dataclasses
    cfg = dataclasses.replace(DANUBE, n_layers=2)
    ctx = single_device_ctx()

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = on_chip(T.abstract_params(cfg, ctx, "serve", jnp.bfloat16))
    state = on_chip(jax.eval_shape(
        lambda: T.init_decode_state(cfg, ctx, 8, 1024, jnp.bfloat16)))
    step = jax.jit(lambda p, s, t: T.decode_step(p, s, t, cfg, ctx),
                   donate_argnums=(1,))
    compiled = step.lower(params, state,
                          _spec(one_chip, (8, 1), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.alias_size_in_bytes > 0, "the cache was not donated"
    assert used < V5E_HBM_BYTES
    cache = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(state["caches"]))
    assert mem.temp_size_in_bytes < cache / 10, "the step copies the cache"
