"""Bring-up check of the real serving path on the TPU.

Serves h2o-danube-3-4b at its published widths (24 layers, d_model 3840,
32/8 heads of 120, vocab 32000) in bfloat16, with random weights drawn from
``--seed``, through ``build_real_engine`` (InferenceEngine + JaxRunner), and
checks what comes out:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # tensor parallel over four chips,
                                     # compared with one chip

The first line names the device; without a TPU the script exits non-zero
and prints no result. Every phase that fails raises, so the exit code is
non-zero. The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.

JAX's persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR says,
or else to .jax_cache/ beside this file.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "h2o-danube-3-4b"
MAX_SLOTS, MAX_LEN = 8, 1024
PROMPT_LENS = (128, 512)      # two prompt lengths: at most two prefill compiles
NEW_TOKENS = (32, 64)         # inclusive range of max_new_tokens
N_REQUESTS = 8
CHECK_STEPS = 4               # decode steps in each logits comparison
# Logits are compared as max |a - b| over max |b|. Weights, activations and
# the cache are bfloat16 (8 significant bits: one step at a logit of 4 is
# 0.03, 0.8% of it), and the two sides round at different points: a cached
# decode step against one pass over the whole sequence, or one chip against
# a four-way split of every matmul. At danube's widths on the CPU this
# measured 1.1% with 2 layers and 1.2% with 6; the noise grows with depth.
# A cache write one position early measured 29-37%, and a decode from an
# empty slot over 100%, so 5% separates noise from a broken cache or shard.
LOGIT_TOL = 0.05


class CompileCounter:
    """Counts the XLA programs this process compiles (or loads from the
    persistent cache) and the seconds they take."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self, phase):
        return (f"[compile] {phase}: programs={self.n} "
                f"seconds={self.seconds:.3f} persistent_cache_hits="
                f"{self.cache_hits}")


def runner_logits(runner, prompt, feed):
    """Logits of one sequence from the runner's own jitted prefill and decode
    steps, in slot 0: the prompt's last position, then one decode step for
    each token of ``feed``. Returns float32 (1 + len(feed), V)."""
    import numpy as np
    out = [runner.prefill_slot(0, prompt)]
    tokens = np.zeros((runner.max_slots,), np.int32)
    active = np.zeros((runner.max_slots,), bool)
    active[0] = True
    for tok in feed:
        tokens[0] = tok
        out.append(runner.decode_slots(tokens, active)[0])
    return np.stack([np.asarray(o, np.float32) for o in out])


def forward_logits(cfg, params, ctx, seq, n_last):
    """The last ``n_last`` positions' logits of one pass of ``T.forward`` over
    ``seq`` (no cache), at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: T.forward(p, t, cfg, ctx, mode="serve")[0])
        logits = fwd(params, jnp.asarray([seq], jnp.int32))[0]
    return np.asarray(logits[-n_last:], np.float32)


def compare(name, got, ref):
    import numpy as np
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite logits"
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    print(f"[check] {name}: max|diff|/max|ref|={err:.6f} tol={LOGIT_TOL} "
          f"shape={list(got.shape)}", flush=True)
    if not err <= LOGIT_TOL:
        raise AssertionError(f"{name}: logits differ by {err} > {LOGIT_TOL}")


def serve(eng, cfg, rng):
    """Submit N_REQUESTS requests and step the engine until it is idle."""
    import jax
    reqs = []
    for i in range(N_REQUESTS):
        prompt = rng.integers(0, cfg.vocab, PROMPT_LENS[i % 2]).tolist()
        n_new = int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1))
        reqs.append(eng.submit(prompt, n_new))
    t0 = time.perf_counter()
    steps = 0
    while eng.step():
        steps += 1
    jax.block_until_ready(eng.runner.state)
    wall = time.perf_counter() - t0
    done = [r for r in reqs if len(r.output) == r.max_new_tokens]
    s = eng.metrics.summary()
    print(f"[serve] finished={s['n_finished']}/{N_REQUESTS} "
          f"exact_lengths={len(done)}/{N_REQUESTS} steps={steps} "
          f"gen_tokens={s['gen_tokens']} prompt_tokens="
          f"{sum(r.isl for r in reqs)} wall_s={wall:.3f} "
          f"preemptions={s['preemptions']}", flush=True)
    if s["n_finished"] != N_REQUESTS or len(done) != N_REQUESTS:
        raise AssertionError(
            f"requests: {[(len(r.output), r.max_new_tokens) for r in reqs]}")


def weight_bytes_by_device(params):
    import jax
    per = collections.Counter()
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            per[shard.device.id] += shard.data.nbytes
    return dict(sorted(per.items()))


def describe(cfg, params):
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    n = sum(x.size for x in leaves)
    dtypes = sorted({str(x.dtype) for x in leaves})
    print(f"[model] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim="
          f"{cfg.resolved_head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"params={n} dtypes={dtypes} max_slots={MAX_SLOTS} "
          f"max_len={MAX_LEN}", flush=True)


def memory_line(devices):
    for d in devices:
        st = d.memory_stats() or {}
        print(f"[memory] device={d.id} peak_bytes_in_use="
              f"{st.get('peak_bytes_in_use')} bytes_in_use="
              f"{st.get('bytes_in_use')} bytes_limit={st.get('bytes_limit')}",
              flush=True)


def one_chip(args, cfg, counter):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import build_real_engine
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    eng = build_real_engine(cfg, dtype=jnp.bfloat16, max_slots=MAX_SLOTS,
                            max_len=MAX_LEN, seed=args.seed)
    jax.block_until_ready((eng.runner.params, eng.runner.state))
    print(f"[init] seconds={time.perf_counter() - t0:.3f}", flush=True)
    describe(cfg, eng.runner.params)
    print(counter.line("init"), flush=True)

    prompt = rng.integers(0, cfg.vocab, PROMPT_LENS[0]).tolist()
    feed = rng.integers(0, cfg.vocab, CHECK_STEPS).tolist()
    got = runner_logits(eng.runner, prompt, feed)
    ref = forward_logits(cfg, eng.runner.params, eng.runner.ctx,
                         prompt + feed, CHECK_STEPS + 1)
    compare("runner prefill+decode vs forward without cache", got, ref)
    print(counter.line("check"), flush=True)

    before = (counter.n, counter.seconds)
    serve(eng, cfg, rng)
    print(counter.line("serve") + f" (during serve: programs="
          f"{counter.n - before[0]} seconds="
          f"{counter.seconds - before[1]:.3f})", flush=True)
    memory_line(jax.devices()[:1])


def four_chips(args, cfg, counter):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import build_real_engine
    from repro.parallel.sharding import ParallelContext, make_mesh
    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs four devices, found {len(devices)}")
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(0, cfg.vocab, PROMPT_LENS[0]).tolist()
    feed = rng.integers(0, cfg.vocab, CHECK_STEPS).tolist()

    def logits_on(ctx):
        eng = build_real_engine(cfg, dtype=jnp.bfloat16, max_slots=MAX_SLOTS,
                                max_len=MAX_LEN, seed=args.seed, ctx=ctx)
        by_dev = weight_bytes_by_device(eng.runner.params)
        total = sum(x.nbytes for x in
                    jax.tree_util.tree_leaves(eng.runner.params))
        print(f"[weights] mesh={None if ctx is None else dict(ctx.mesh.shape)} "
              f"total_bytes={total} bytes_by_device={by_dev}", flush=True)
        out = runner_logits(eng.runner, prompt, feed)
        memory_line(devices)
        return out, by_dev, total

    ref, _, _ = logits_on(None)
    gc.collect()
    print(counter.line("one chip"), flush=True)
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices)
    got, by_dev, total = logits_on(ParallelContext(mesh=mesh))
    print(counter.line("four chips"), flush=True)
    shares = {d: b / total for d, b in by_dev.items()}
    print(f"[weights] share_by_device={shares}", flush=True)
    if len(shares) != 4 or any(abs(s - 0.25) > 0.02 for s in shares.values()):
        raise AssertionError(f"weights not split four ways: {shares}")
    compare("four chips (data=1, model=4) vs one chip", got, ref)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()
    d0 = devices[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        sys.exit(f"no TPU: JAX found {d0.platform}")
    print(f"[compile] cache_dir={jax.config.jax_compilation_cache_dir}",
          flush=True)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.registry import get_config
    counter = CompileCounter()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args, cfg, counter)
    print(f"[done] wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
