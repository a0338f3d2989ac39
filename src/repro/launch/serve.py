"""Serving launcher.

Real mode, float32 at reduced widths (runs on the CPU):
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
        --requests 8

Real mode, bfloat16 at published widths (needs an accelerator that holds the
weights):
    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-3-4b \
        --requests 8 --max-num-seqs 8

Simulated fleet mode (paper-scale characterization):
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-405b --sim \
        --hw h200 --tp 8 --requests 2000
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs.registry import get_config, get_smoke_config
from repro.core import perf_model as pm
from repro.core.engine import EngineConfig, InferenceEngine
from repro.core.router import DPRouter, RouterConfig
from repro.core.runner import JaxRunner, SimRunner
from repro.data.reasoning import REASONING, sample

PAGE = 16      # KV page size (tokens) of the real-mode engine


def build_sim_fleet(cfg, args):
    hw = {"h200": pm.H200, "v5e": pm.V5E}[args.hw]
    plan = pm.ParallelismPlan(dp=args.dp, tp=args.tp, pp=args.pp, ep=args.tp)
    cap = pm.kv_capacity_tokens(cfg, plan, hw)
    ecfg = EngineConfig(n_pages=max(cap // 16, 64),
                        max_num_seqs=args.max_num_seqs,
                        max_num_batched_tokens=args.max_batched_tokens,
                        chunk_size=512, admission_mode=args.admission,
                        autotune=args.autotune)
    replicas = [InferenceEngine(cfg, ecfg, SimRunner(cfg, plan, hw))
                for _ in range(args.dp)]
    return DPRouter(replicas, RouterConfig(policy=args.router))


def build_real_engine(cfg, *, dtype, max_slots: int, max_len: int,
                      seed: int = 0, ctx=None,
                      admission_mode: str = "kv_aware") -> InferenceEngine:
    """The real serving path: random weights drawn from ``seed`` in
    ``dtype``, a ``JaxRunner`` with ``max_slots`` cache slots of ``max_len``
    positions, and an engine whose page pool and concurrency cap hold
    exactly those slots. ``ctx`` (default: one device) places the weights
    and the cache."""
    import jax
    from repro.models import transformer as T
    from repro.parallel.sharding import single_device_ctx
    ctx = ctx or single_device_ctx()
    params = T.init_params(cfg, jax.random.PRNGKey(seed), ctx, mode="serve",
                           dtype=dtype)
    runner = JaxRunner(cfg, params, ctx, max_slots=max_slots, max_len=max_len)
    ecfg = EngineConfig(n_pages=max_slots * max_len // PAGE,
                        max_num_seqs=max_slots,
                        max_num_batched_tokens=max_len, chunk_size=max_len,
                        page_size=PAGE, admission_mode=admission_mode)
    return InferenceEngine(cfg, ecfg, runner, virtual_clock=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sim", action="store_true")
    ap.add_argument("--hw", choices=["h200", "v5e"], default="v5e")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--max-num-seqs", type=int, default=256)
    ap.add_argument("--max-batched-tokens", type=int, default=8192)
    ap.add_argument("--admission", choices=["naive", "kv_aware"],
                    default="kv_aware")
    ap.add_argument("--router", choices=["round_robin", "jsq", "memory_aware"],
                    default="memory_aware")
    ap.add_argument("--autotune", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.sim:
        cfg = get_config(args.arch)
        router = build_sim_fleet(cfg, args)
        for isl, osl in sample(REASONING, args.requests, seed=args.seed):
            router.submit(int(isl), int(osl), arrival=0.0)
        metrics = router.run_all()
        agg = {}
        for i, m in enumerate(metrics):
            s = m.summary()
            print(f"[replica {i}] done={s['n_finished']} "
                  f"tput={s['gen_throughput_tok_s']:.0f} tok/s "
                  f"ttft_p50={s['ttft_s']['p50']:.2f}s "
                  f"tpot={s['tpot_s']['mean']*1e3:.1f}ms "
                  f"preempt={s['preemptions']}")
        total = sum(m.summary()["gen_tokens"] for m in metrics)
        dur = max(m.summary()["duration_s"] for m in metrics)
        print(f"[fleet] {total} tokens in {dur:.1f}s "
              f"-> {total/dur:.0f} tok/s aggregate")
        return

    # real execution
    import jax.numpy as jnp
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(args.seed)
    reqs = [(rng.integers(0, cfg.vocab, size=int(rng.integers(4, 24))).tolist(),
             int(rng.integers(8, 32))) for _ in range(args.requests)]
    # slots sized to the workload: the longest request, rounded to a page
    longest = max(len(p) + n for p, n in reqs)
    eng = build_real_engine(
        cfg, dtype=jnp.float32 if args.smoke else jnp.bfloat16,
        max_slots=min(args.max_num_seqs, args.requests),
        max_len=-(-longest // PAGE) * PAGE, seed=args.seed,
        admission_mode=args.admission)
    for prompt, n_new in reqs:
        eng.submit(prompt, n_new)
    m = eng.run()
    s = m.summary()
    print(json.dumps({k: v for k, v in s.items() if not isinstance(v, dict)},
                     indent=1))
    print(f"[serve] completed {s['n_finished']} requests, "
          f"{s['gen_tokens']} tokens")


if __name__ == "__main__":
    main()
