"""JaxRunner's masked decode step: a slot left out of a step keeps every
byte of its state, the slots that step match an unmasked decode, a paused
request resumes with the tokens it would have had, and the decode program
merges no whole cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.paper_models import DEEPSEEK_R1_671B
from repro.configs.registry import get_smoke_config
from repro.core.engine import EngineConfig, InferenceEngine
from repro.core.runner import JaxRunner
from repro.models import transformer as T
from repro.parallel.sharding import single_device_ctx

CTX = single_device_ctx()
MAX_LEN = 16


def _config(family):
    if family == "mla":
        return reduced(DEEPSEEK_R1_671B)
    return get_smoke_config({"dense": "llama3.2-3b", "hybrid": "zamba2-2.7b",
                             "ssm": "xlstm-350m"}[family])


def _host(tree):
    return jax.tree_util.tree_map(np.array, tree)


def _slot(leaf, axes, s):
    return np.take(leaf, s, axis=axes.index("cache_batch"))


@pytest.mark.parametrize("family", ["dense", "mla", "hybrid", "ssm"])
def test_inactive_slots_keep_their_state(family):
    """Slots 1 and 3 sit out a step: every leaf of theirs is bit-identical
    afterwards at every position, ``lens`` included. Slot 3 is full
    (``lens == max_len``). The active slots' logits and state equal
    ``decode_step`` with every slot active."""
    cfg = _config(family)
    params = T.init_params(cfg, jax.random.PRNGKey(0), CTX, mode="serve",
                           dtype=jnp.float32)
    runner = JaxRunner(cfg, params, CTX, max_slots=4, max_len=MAX_LEN)
    rng = np.random.default_rng(0)
    for slot, n in enumerate((5, 9, 3, MAX_LEN)):
        runner.prefill_slot(slot, rng.integers(0, cfg.vocab, n).tolist())
    before = _host(runner.state)
    tokens = rng.integers(0, cfg.vocab, 4).astype(np.int32)
    active = np.array([True, False, True, False])
    logits = np.asarray(runner.decode_slots(tokens, active))
    after = _host(runner.state)
    ref_logits, ref_state = T.decode_step(
        params, jax.tree_util.tree_map(jnp.asarray, before),
        jnp.asarray(tokens[:, None]), cfg, CTX)
    ref_state = _host(ref_state)
    axes = jax.tree_util.tree_leaves(T.decode_state_axes(cfg),
                                     is_leaf=T._is_axes)
    leaves = zip(axes, *(jax.tree_util.tree_leaves(t)
                         for t in (before, after, ref_state)))
    for ax, old, new, ref in leaves:
        for s in (1, 3):
            np.testing.assert_array_equal(_slot(new, ax, s), _slot(old, ax, s))
        for s in (0, 2):
            np.testing.assert_allclose(_slot(new, ax, s), _slot(ref, ax, s),
                                       rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(after["lens"], before["lens"] + active)
    np.testing.assert_allclose(logits[active],
                               np.asarray(ref_logits)[active, 0],
                               rtol=1e-5, atol=1e-5)


def _greedy(cfg, params, prompt, n_new):
    last, state = T.prefill(params, jnp.asarray([prompt], jnp.int32), cfg,
                            CTX, max_len=64, cache_dtype=jnp.float32)
    out = [int(jnp.argmax(last[0]))]
    for _ in range(n_new - 1):
        logits, state = T.decode_step(
            params, state, jnp.asarray([[out[-1]]], jnp.int32), cfg, CTX)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_paused_slot_resumes_with_its_tokens(family):
    """Chunked prefill finishes the prompts in different steps, so each
    request's slot is live but left out of the decode step that runs beside
    its prefill, and slots are reused. Every request still produces its
    greedy continuation, as if it had never paused."""
    cfg = _config(family)
    params = T.init_params(cfg, jax.random.PRNGKey(1), CTX, mode="serve",
                           dtype=jnp.float32)
    runner = JaxRunner(cfg, params, CTX, max_slots=3, max_len=64)
    ecfg = EngineConfig(n_pages=64, max_num_seqs=3, max_num_batched_tokens=8,
                        chunk_size=4, admission_mode="naive")
    eng = InferenceEngine(cfg, ecfg, runner, virtual_clock=False)
    eng.events.enable_recording()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (3, 9, 14, 6)]
    n_new = [9, 6, 5, 4]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
    eng.run(max_steps=500)
    # a request whose prefill completes is left out of the decode step that
    # follows in the same engine step
    paused, prefilled = set(), set()
    for e in eng.events.events:
        if e.kind == "prefill" and e.payload["completing"]:
            prefilled.add(e.rid)
        elif e.kind == "decode_step":
            paused |= prefilled - set(e.payload["rids"])
            prefilled.clear()
    assert len(paused) >= 2
    for p, n, r in zip(prompts, n_new, reqs):
        assert r.output == _greedy(cfg, params, p, n)


def _cache_types(cfg, state):
    """The StableHLO tensor types of the positional cache leaves."""
    axes = jax.tree_util.tree_leaves(T.decode_state_axes(cfg),
                                     is_leaf=T._is_axes)
    dtypes = {jnp.float32: "f32", jnp.bfloat16: "bf16"}
    return {f"tensor<{'x'.join(map(str, a.shape))}x{dtypes[a.dtype.type]}>"
            for ax, a in zip(axes, jax.tree_util.tree_leaves(state))
            if "cache_seq" in ax}


@pytest.mark.parametrize("family", ["dense", "mla", "hybrid"])
def test_decode_program_selects_no_whole_cache(family):
    """The runner's lowered decode program holds no ``select`` whose result
    has the shape of a positional cache leaf: inactive slots are kept by
    merging nothing into them, not by a masked merge of the whole cache."""
    cfg = _config(family)
    params = T.init_params(cfg, jax.random.PRNGKey(0), CTX, mode="serve",
                           dtype=jnp.float32)
    runner = JaxRunner(cfg, params, CTX, max_slots=4, max_len=MAX_LEN)
    types = _cache_types(cfg, runner.state)
    assert types
    text = runner._decode_fn.lower(
        params, runner.state, jnp.zeros((4, 1), jnp.int32),
        jnp.array([True, False, True, False])).as_text()
    for line in text.splitlines():
        if "stablehlo.select" in line:
            assert not any(t in line for t in types), line
