"""Per-arch smoke tests (brief deliverable f): reduced config, one forward /
train step on CPU, shape + finiteness asserts, plus prefill->decode
consistency for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.paper_models import DEEPSEEK_R1_671B
from repro.configs.registry import ARCHS, get_smoke_config
from repro.models import transformer as T
from repro.parallel.sharding import single_device_ctx
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step

CTX = single_device_ctx()
KEY = jax.random.PRNGKey(0)


def _tokens(cfg, b=2, s=16):
    return jax.random.randint(KEY, (b, s), 0, cfg.vocab)


def _prefix(cfg, b=2):
    if not cfg.frontend_prefix_len:
        return None
    return jax.random.normal(KEY, (b, cfg.frontend_prefix_len, cfg.d_model))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_smoke(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, KEY, CTX, mode="train", dtype=jnp.float32)
    logits, _ = T.forward(params, _tokens(cfg), cfg, CTX, mode="train",
                          prefix_embeds=_prefix(cfg))
    s_total = 16 + cfg.frontend_prefix_len
    assert logits.shape == (2, s_total, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ["llama3.2-3b", "phi3.5-moe-42b-a6.6b",
                                  "zamba2-2.7b", "xlstm-350m",
                                  "internvl2-76b"])
def test_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=1)
    params = T.init_params(cfg, KEY, CTX, mode="train", dtype=jnp.float32)
    opt = init_opt_state(params, ocfg)
    tokens = _tokens(cfg, 2, 16)
    batch = {"tokens": tokens, "labels": tokens}
    pre = _prefix(cfg)
    if pre is not None:
        batch["prefix_embeds"] = pre
    step = jax.jit(make_train_step(cfg, CTX, ocfg))
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    delta = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.abs(l).sum()),
        jax.tree_util.tree_map(lambda a, b: a - b, params, params2), 0.0)
    assert delta > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_decode_consistency(arch):
    cfg = get_smoke_config(arch)
    params = T.init_params(cfg, KEY, CTX, mode="serve", dtype=jnp.float32)
    tokens = _tokens(cfg, 2, 12)
    nxt = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0, cfg.vocab)
    full = jnp.concatenate([tokens, nxt], axis=1)
    logits_full, _ = T.forward(params, full, cfg, CTX, mode="serve")
    last, state = T.prefill(params, tokens, cfg, CTX, max_len=16,
                            cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(logits_full[:, 11]),
                               rtol=3e-4, atol=3e-4)
    dec, state = T.decode_step(params, state, nxt, cfg, CTX)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(logits_full[:, 12]),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_state_axes_match_state(arch):
    """decode_state_axes mirrors init_decode_state leaf for leaf, and its
    "cache_batch" axis is the one axis that grows with the batch."""
    cfg = get_smoke_config(arch)
    shapes = [jax.eval_shape(lambda: T.init_decode_state(cfg, CTX, b, 32))
              for b in (3, 5)]
    axes = jax.tree_util.tree_leaves(T.decode_state_axes(cfg),
                                     is_leaf=T._is_axes)
    slots = jax.tree_util.tree_leaves(T.decode_slot_axes(cfg))
    leaves = [jax.tree_util.tree_leaves(s) for s in shapes]
    assert len(axes) == len(slots) == len(leaves[0])
    for a, ax, small, big in zip(axes, slots, *leaves):
        grown = [i for i in range(small.ndim)
                 if small.shape[i] != big.shape[i]]
        assert len(a) == small.ndim and grown == [ax], (a, small.shape)


def test_mla_paper_model():
    cfg = reduced(DEEPSEEK_R1_671B)
    params = T.init_params(cfg, KEY, CTX, mode="serve", dtype=jnp.float32)
    tokens = _tokens(cfg, 2, 12)
    nxt = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0, cfg.vocab)
    full = jnp.concatenate([tokens, nxt], axis=1)
    logits_full, _ = T.forward(params, full, cfg, CTX, mode="serve")
    last, state = T.prefill(params, tokens, cfg, CTX, max_len=16,
                            cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(logits_full[:, 11]),
                               rtol=3e-4, atol=3e-4)
    # the MLA decode cache is the compressed latent, not per-head KV
    ckv = state["caches"]["moe_stack"]["ckv"]
    assert ckv.shape[-1] == cfg.mla.kv_lora_rank
    dec, _ = T.decode_step(params, state, nxt, cfg, CTX)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(logits_full[:, 12]),
                               rtol=3e-3, atol=3e-3)


def test_swa_decode_masks_outside_window():
    """Sliding-window decode attention must ignore keys beyond the window
    (single-op test: multi-layer receptive fields legitimately exceed w)."""
    from repro.models.attention import decode_attention
    B, S, H, KV, D, w = 2, 32, 4, 2, 16, 8
    q = jax.random.normal(KEY, (B, 1, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, D))
    lens = jnp.full((B,), 20)
    out1 = decode_attention(q, k, v, lens, window=w)
    # perturb cache strictly outside the window (positions <= 20 - 8)
    k2 = k.at[:, :12].set(jax.random.normal(jax.random.PRNGKey(3),
                                            (B, 12, KV, D)))
    v2 = v.at[:, :12].set(jax.random.normal(jax.random.PRNGKey(4),
                                            (B, 12, KV, D)))
    out2 = decode_attention(q, k2, v2, lens, window=w)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-5)
    # ...and the window must actually matter vs full attention
    out_full = decode_attention(q, k2, v2, lens, window=0)
    assert float(jnp.abs(out_full - out2).max()) > 1e-3


def test_decode_unroll_and_2dtp_match_scan():
    """§Perf levers preserve semantics: unrolled decode == scan decode."""
    from repro.parallel.sharding import ParallelContext
    cfg = get_smoke_config("llama3.2-3b")
    params = T.init_params(cfg, KEY, CTX, mode="serve", dtype=jnp.float32)
    tokens = _tokens(cfg, 2, 10)
    nxt = jax.random.randint(jax.random.PRNGKey(5), (2, 1), 0, cfg.vocab)
    outs = []
    for ctx in (ParallelContext(mesh=None),
                ParallelContext(mesh=None, decode_unroll=True,
                                serve_2d_tp=True)):
        last, st = T.prefill(params, tokens, cfg, ctx, max_len=16,
                             cache_dtype=jnp.float32)
        dec, _ = T.decode_step(params, st, nxt, cfg, ctx)
        outs.append(np.asarray(dec))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5, atol=1e-5)


def test_int8_kv_cache_decode_runs():
    """int8 KV cache (capacity lever) stays finite and roughly consistent."""
    from repro.parallel.sharding import ParallelContext
    cfg = get_smoke_config("llama3.2-3b")
    params = T.init_params(cfg, KEY, CTX, mode="serve", dtype=jnp.float32)
    tokens = _tokens(cfg, 2, 10)
    nxt = jax.random.randint(jax.random.PRNGKey(5), (2, 1), 0, cfg.vocab)
    ctx = ParallelContext(mesh=None, kv_cache_dtype=jnp.int8)
    last, st = T.prefill(params, tokens, cfg, ctx, max_len=16,
                         cache_dtype=jnp.int8)
    assert st["caches"]["dense_stack"]["k"].dtype == jnp.int8
    dec, _ = T.decode_step(params, st, nxt, cfg, ctx)
    assert bool(jnp.isfinite(dec).all())
