"""End-to-end engine behaviour: real-execution correctness (engine output ==
straight-line greedy decode, WITH and WITHOUT forced preemption), sim-mode
capacity-trap dynamics, autotuner, and DP routing; the real-mode wall
clock, host spans and program names."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core import perf_model as pm
from repro.core.engine import EngineConfig, InferenceEngine
from repro.core.router import DPRouter, RouterConfig
from repro.core.runner import JaxRunner, SimRunner
from repro.models import transformer as T
from repro.parallel.sharding import single_device_ctx

CTX = single_device_ctx()


def _greedy_reference(cfg, params, prompt, n_new):
    tokens = jnp.asarray([prompt], jnp.int32)
    last, state = T.prefill(params, tokens, cfg, CTX, max_len=192,
                            cache_dtype=jnp.float32)
    out = [int(jnp.argmax(last[0]))]
    for _ in range(n_new - 1):
        logits, state = T.decode_step(
            params, state, jnp.asarray([[out[-1]]], jnp.int32), cfg, CTX)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("llama3.2-3b")
    params = T.init_params(cfg, jax.random.PRNGKey(0), CTX, mode="serve",
                           dtype=jnp.float32)
    return cfg, params


def _run_engine(cfg, params, prompts, n_new, n_pages, max_slots=4):
    runner = JaxRunner(cfg, params, CTX, max_slots=max_slots, max_len=192)
    ecfg = EngineConfig(n_pages=n_pages, max_num_seqs=max_slots,
                        max_num_batched_tokens=512, chunk_size=192,
                        admission_mode="naive")
    eng = InferenceEngine(cfg, ecfg, runner, virtual_clock=False)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
    eng.run(max_steps=2000)
    return reqs


def test_engine_matches_greedy(small_model):
    cfg, params = small_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist()
               for n in (7, 11, 5)]
    n_new = [6, 4, 8]
    reqs = _run_engine(cfg, params, prompts, n_new, n_pages=64)
    for p, n, r in zip(prompts, n_new, reqs):
        assert r.output == _greedy_reference(cfg, params, p, n)


def test_engine_slots_equal_to_kv_heads(small_model):
    """max_slots equal to the KV-head count (and the layer count): each
    cache leaf's slot axis is declared by the model, not found by size."""
    cfg, params = small_model
    assert cfg.n_kv_heads == cfg.n_layers == 2
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist() for n in (9, 4, 6)]
    n_new = [5, 7, 3]
    reqs = _run_engine(cfg, params, prompts, n_new, n_pages=64,
                       max_slots=cfg.n_kv_heads)
    for p, n, r in zip(prompts, n_new, reqs):
        assert r.output == _greedy_reference(cfg, params, p, n)


def test_runner_refuses_request_longer_than_slot(small_model):
    """A request whose prompt and output overrun its slot is refused: the
    device would drop the out-of-range cache writes without an error."""
    cfg, params = small_model
    runner = JaxRunner(cfg, params, CTX, max_slots=2, max_len=16)
    eng = InferenceEngine(cfg, EngineConfig(n_pages=8, max_num_seqs=2,
                                            max_num_batched_tokens=64,
                                            chunk_size=16),
                          runner, virtual_clock=False)
    eng.submit(list(range(10)), 8)
    with pytest.raises(ValueError, match="16-position slot"):
        eng.run(max_steps=10)


def test_build_real_engine_serves_smoke_config():
    """The real-mode builder shared by the serve launcher and the chip
    smoke check serves every request to its requested length, and its
    outputs are the greedy continuation of its own weights."""
    from repro.launch.serve import build_real_engine
    cfg = get_smoke_config("h2o-danube-3-4b")
    eng = build_real_engine(cfg, dtype=jnp.float32, max_slots=3, max_len=64,
                            seed=5)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist()
               for n in (12, 30, 7, 20)]
    n_new = [10, 6, 12, 9]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
    eng.run(max_steps=2000)
    assert eng.metrics.summary()["n_finished"] == len(reqs)
    params = eng.runner.params
    assert params["embed"].dtype == jnp.float32
    for p, n, r in zip(prompts, n_new, reqs):
        assert len(r.output) == n
        assert r.output == _greedy_reference(cfg, params, p, n)


def test_engine_preemption_preserves_outputs(small_model):
    """With a pool sized to force preemption+recompute, outputs must still be
    exactly the unconstrained greedy continuation (§IV-A recompute path)."""
    cfg, params = small_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=30).tolist() for _ in range(3)]
    n_new = [20, 20, 20]
    reqs = _run_engine(cfg, params, prompts, n_new, n_pages=7)
    assert sum(r.n_preemptions for r in reqs) > 0, \
        "pool was sized to force preemption"
    for p, n, r in zip(prompts, n_new, reqs):
        assert r.output == _greedy_reference(cfg, params, p, n)


def _smoke_engine(cfg, params, max_slots=2):
    runner = JaxRunner(cfg, params, CTX, max_slots=max_slots, max_len=192)
    ecfg = EngineConfig(n_pages=64, max_num_seqs=max_slots,
                        max_num_batched_tokens=512, chunk_size=192)
    return InferenceEngine(cfg, ecfg, runner, virtual_clock=False)


def test_real_mode_clock_is_the_wall_clock(small_model):
    """A real-mode engine stamps requests and events with
    time.perf_counter(): a request that waits before the first step has
    waited, a future arrival is waited for and never jumped to, and every
    timestamp lies between the clock reads around the calls that made it."""
    cfg, params = small_model
    eng = _smoke_engine(cfg, params)
    eng.events.enable_recording()
    rng = np.random.default_rng(3)
    t0 = time.perf_counter()
    first = eng.submit(rng.integers(0, cfg.vocab, 9).tolist(), 4)
    t1 = time.perf_counter()
    time.sleep(0.2)
    eng.run(max_steps=100)
    t2 = time.perf_counter()
    later = eng.submit(rng.integers(0, cfg.vocab, 6).tolist(), 3,
                       arrival=t2 + 0.2)
    eng.run(max_steps=100)
    t3 = time.perf_counter()
    assert t0 <= first.arrival <= t1
    assert first.waiting_time() >= 0.2
    assert first.t_finished <= t2 < later.arrival == t2 + 0.2
    for r in (first, later):
        assert r.arrival <= r.t_admitted <= r.t_first_token
        assert [r.t_first_token, *r.decode_times] == \
            sorted([r.t_first_token, *r.decode_times])
        assert r.t_finished == r.decode_times[-1] <= t3
        assert len(r.output) == r.max_new_tokens
    times = [e.t for e in eng.events.events]
    assert times == sorted(times) and t0 <= times[0] and times[-1] <= t3
    with pytest.raises(ValueError, match="wall clock"):
        eng.advance_to(t3 + 1.0)


def test_real_mode_spans_in_a_profiler_trace(small_model, tmp_path):
    """Under jax.profiler the engine's and runner's host spans are in the
    trace, each inside its parent, with their stats as event stats."""
    from jax.profiler import ProfileData
    from repro.trace.annotate import SPAN_NAMES
    cfg, params = small_model
    eng = _smoke_engine(cfg, params)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (7, 11)]
    for p in prompts:                     # compile outside the trace
        eng.submit(p, 3)
    eng.run(max_steps=100)
    reqs = [eng.submit(p, 3) for p in prompts]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(max_steps=100)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.")]
    assert {s[0] for s in spans} == set(SPAN_NAMES)
    parent = {"repro.scheduler.plan_step": "repro.engine.step",
              "repro.runner.prefill": "repro.engine.step",
              "repro.runner.decode": "repro.engine.step",
              "repro.runner.prefill.dispatch": "repro.runner.prefill",
              "repro.runner.prefill.wait": "repro.runner.prefill",
              "repro.runner.decode.dispatch": "repro.runner.decode",
              "repro.runner.decode.wait": "repro.runner.decode"}
    for name, s, e, _ in spans:
        if name in parent:
            assert any(n == parent[name] and ps <= s and e <= pe
                       for n, ps, pe, _ in spans), name
    stats = lambda n: [st for name, _, _, st in spans if name == n]  # noqa
    assert sorted(st["rid"] for st in stats("repro.runner.prefill")) == \
        [r.rid for r in reqs]
    assert all(st["n"] in (1, 2) for st in stats("repro.runner.decode"))
    assert all("step_num" in st for st in stats("repro.engine.step"))


def test_runner_programs_have_stable_names(small_model):
    """The jitted steps compile to modules named after what they do, which
    is how a device trace names their operations."""
    cfg, params = small_model
    runner = JaxRunner(cfg, params, CTX, max_slots=2, max_len=64)
    tokens = jnp.zeros((1, 8), jnp.int32)
    _, fresh = runner._prefill_fn(params, tokens)
    lowered = {
        "jit_prefill": runner._prefill_fn.lower(params, tokens),
        "jit_insert": runner._insert_fn.lower(runner.state, fresh, 0),
        "jit_decode": runner._decode_fn.lower(
            params, runner.state, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), bool))}
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text()


def _sim_engine(cfg, max_seqs, n_pages, admission="naive", autotune=False):
    ecfg = EngineConfig(n_pages=n_pages, max_num_seqs=max_seqs,
                        max_num_batched_tokens=4096, chunk_size=256,
                        admission_mode=admission, autotune=autotune)
    return InferenceEngine(
        cfg, ecfg, SimRunner(cfg, pm.ParallelismPlan(), pm.H200))


def test_sim_capacity_trap_dynamics():
    """Obs 1/2: TTFT falls and TPOT rises with concurrency; oversubscription
    triggers preemption."""
    from repro.configs.paper_models import DS_DISTILL_8B
    cfg = DS_DISTILL_8B
    res = {}
    for ms in (16, 256):
        eng = _sim_engine(cfg, ms, n_pages=3000)
        for _ in range(120):
            eng.submit(100, 600, arrival=0.0)
        s = eng.run(max_steps=50000).summary()
        res[ms] = s
    assert res[256]["ttft_s"]["p50"] < res[16]["ttft_s"]["p50"]
    assert res[256]["tpot_s"]["mean"] > res[16]["tpot_s"]["mean"]
    assert res[256]["preemptions"] > 0
    assert res[16]["preemptions"] == 0


def test_kv_aware_admission_prevents_preemption_in_sim():
    from repro.configs.paper_models import DS_DISTILL_8B
    cfg = DS_DISTILL_8B
    naive = _sim_engine(cfg, 256, 3000, admission="naive")
    aware = _sim_engine(cfg, 256, 3000, admission="kv_aware")
    for eng in (naive, aware):
        for _ in range(120):
            eng.submit(100, 600, arrival=0.0)
    sn = naive.run(max_steps=50000).summary()
    sa = aware.run(max_steps=50000).summary()
    assert sn["preemptions"] > 0
    assert sa["preemptions"] == 0
    assert sa["recomputed_tokens"] == 0


def test_resumed_request_context_len_not_inflated():
    """Regression: completing a recompute-resume used to zero resume_extra
    without folding the regenerated prefix out of prompt_pos, so context_len
    double-counted it — every preempted-then-resumed request held phantom KV
    pages for the rest of its decode (found by the sim sanitizer's
    used <= isl + generated + 1 invariant)."""
    from repro.configs.paper_models import DS_DISTILL_8B
    eng = _sim_engine(DS_DISTILL_8B, 256, 3000, admission="naive")
    from repro.lint.sanitizer import EngineSanitizer
    eng._sanitizer = EngineSanitizer(eng)
    for _ in range(120):
        eng.submit(100, 600, arrival=0.0)
    s = eng.run(max_steps=50000).summary()   # sanitizer checks every step
    assert s["preemptions"] > 0, "pool was sized to force preemption"
    for r in eng.metrics.finished:
        assert r.resume_extra == 0
        assert r.context_len == r.isl + r.generated, vars(r)


def test_autotuner_backs_off():
    from repro.configs.paper_models import DS_DISTILL_8B
    cfg = DS_DISTILL_8B
    eng = _sim_engine(cfg, 512, 2000, admission="naive", autotune=True)
    for _ in range(200):
        eng.submit(100, 500, arrival=0.0)
    eng.run(max_steps=50000)
    assert eng.sched.cfg.max_num_seqs < 512, "autotuner should shed concurrency"


def test_memory_aware_router_balances():
    from repro.configs.paper_models import DS_DISTILL_8B
    cfg = DS_DISTILL_8B
    replicas = [_sim_engine(cfg, 64, 2000) for _ in range(4)]
    router = DPRouter(replicas, RouterConfig(policy="memory_aware"))
    for i in range(160):
        router.submit(100, 400, arrival=0.0)
    counts = [len(e.sched.waiting) + len(e.sched.running) for e in replicas]
    assert max(counts) - min(counts) <= 2, f"imbalanced routing: {counts}"
    router.run_all()
    done = sum(e.metrics.summary()["n_finished"] for e in replicas)
    assert done == 160
