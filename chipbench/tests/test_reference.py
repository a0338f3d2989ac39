"""The plain reference against the program at small widths on the CPU.
The reference never imports the program; these tests do, to hold them
together."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference
from chipbench.run import model_config

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def tiny(name, **over):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=4, hidden_size=64, num_attention_heads=8,
               num_key_value_heads=4, head_dim=16, intermediate_size=96,
               vocab_size=128, **over)
    return cfg


CONFIGS = [tiny("danube3-4b", sliding_window=48), tiny("qwen3-14b-tp4")]


def program_params(cfg, seed, dtype, ctx=None):
    from repro.models import transformer as T
    from repro.parallel.sharding import single_device_ctx
    return T.init_params(model_config(cfg), jax.random.PRNGKey(seed),
                         ctx or single_device_ctx(), mode="serve",
                         dtype=dtype)


def flat(params):
    return {"/".join(str(k.key) for k in p): np.asarray(v.astype(jnp.float32))
            for p, v in jax.tree_util.tree_leaves_with_path(params)}


@pytest.mark.parametrize("cfg", CONFIGS, ids=["danube", "qwen3"])
def test_weights_follow_the_programs_recipe(cfg):
    seed = 2**31 + 3
    ref = {k: np.asarray(v.astype(jnp.float32))
           for k, v in reference.draw(cfg, seed, jax.devices()[:1]).items()}
    assert ref.keys() == flat(program_params(cfg, seed, jnp.bfloat16)).keys()
    for k, v in flat(program_params(cfg, seed, jnp.bfloat16)).items():
        np.testing.assert_array_equal(ref[k], v, err_msg=k)


def test_weights_do_not_depend_on_placement():
    """Four devices, the program on its (data=1, model=4) mesh and the
    reference with its layers spread over the devices: the same values."""
    from repro.parallel.sharding import ParallelContext, make_mesh
    cfg = CONFIGS[1]
    devs = jax.devices()[:4]
    assert len(devs) == 4
    ctx = ParallelContext(mesh=make_mesh((1, 4), ("data", "model"),
                                         devices=devs))
    prog = flat(program_params(cfg, 11, jnp.bfloat16, ctx))
    ref = reference.draw(cfg, 11, devs)
    assert len(ref["dense_stack/wq"].addressable_shards) == 4
    for k, v in prog.items():
        np.testing.assert_array_equal(np.asarray(
            ref[k].astype(jnp.float32)), v, err_msg=k)


@pytest.mark.parametrize("cfg", CONFIGS, ids=["danube", "qwen3"])
def test_logits_match_the_programs_forward_in_float32(cfg):
    """At float32 weights the reference and the program's forward pass
    (no cache, highest precision) agree to float32 rounding, across a
    sequence longer than the window."""
    from repro.models import transformer as T
    from repro.parallel.sharding import single_device_ctx
    seed, n = 5, 130
    params = program_params(cfg, seed, jnp.float32)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], n)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(T.forward(params, jnp.asarray([toks], jnp.int32),
                                    model_config(cfg), single_device_ctx(),
                                    mode="serve")[0][0])
    ref = reference.Reference(dict(cfg, dtype="float32"), seed,
                              jax.devices()[:1])
    ref.w = {k: v.astype(jnp.float32) for k, v in
             reference.draw(cfg, seed, jax.devices()[:1],
                            dtype=jnp.float32).items()}
    got = ref.logits([toks.tolist()], [list(range(n))])[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_window_changes_the_answer():
    """The window is part of the reference: a 48-position window gives
    other logits than none once the sequence is longer than it."""
    cfg = CONFIGS[0]
    toks = np.random.default_rng(1).integers(0, 128, 100).tolist()
    a = reference.Reference(cfg, 3, jax.devices()[:1]).logits(
        [toks], [[10, 99]])[0]
    b = reference.Reference(dict(cfg, sliding_window=None), 3,
                            jax.devices()[:1]).logits([toks], [[10, 99]])[0]
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5, atol=1e-5)
    assert np.abs(a[1] - b[1]).max() > 1e-3


def test_fp8_rounds_to_eight_bits_per_channel():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.01
    q = reference.fp8(w, (0,))
    rel = np.abs(np.asarray(q - w)) / np.abs(np.asarray(w)).max(0)
    assert 0 < rel.max() <= 2.0 ** -4 * 1.01      # e4m3: 3 mantissa bits
