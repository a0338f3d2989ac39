import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Multi-pod dry-run: lower + compile every (arch x shape) cell on the
# production mesh with 512 placeholder host devices (the two lines above MUST
# precede any other import — jax locks the device count at first init).
import argparse    # noqa: E402
import json        # noqa: E402
import time        # noqa: E402
import traceback   # noqa: E402
from pathlib import Path  # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis.hlo import analyze_compiled          # noqa: E402
from repro.configs.registry import (ARCHS, SHAPES, cells,  # noqa: E402
                                    get_config, shape_applicable)
from repro.launch.mesh import make_production_mesh        # noqa: E402
from repro.launch.specs import build_ctx, input_specs     # noqa: E402
from repro.models import transformer as T                 # noqa: E402
from repro.train import optimizer as opt_lib              # noqa: E402
from repro.train.train_step import (make_decode_step,     # noqa: E402
                                    make_prefill_step, make_train_step)

# v5e hardware constants for the roofline terms (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link (wire-bytes basis)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               opts=None, return_artifacts: bool = False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = build_ctx(mesh, multi_pod, cfg, shape, opts)
    opts = opts or {}
    mode = "train" if shape.kind == "train" else "serve"
    params_dtype = jnp.bfloat16
    aparams = T.abstract_params(cfg, ctx, mode=mode, dtype=params_dtype)
    psh = T.param_shardings(cfg, ctx, mode=mode)
    spec = input_specs(cfg, shape, ctx)
    # lint: disable=REP002 (measuring real lower/compile wall time, not sim)
    t0 = time.time()

    if shape.kind == "train":
        ocfg = opt_lib.AdamWConfig(
            state_dtype=jnp.bfloat16 if opts.get("opt_bf16") else jnp.float32)
        aopt = opt_lib.abstract_opt_state(aparams, ocfg)
        osh = opt_lib.opt_state_shardings(psh, mesh)
        step = make_train_step(cfg, ctx, ocfg)
        jitted = jax.jit(step, in_shardings=(psh, osh, spec["shardings"]),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(aparams, aopt, spec["batch"])
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, ctx, max_len=shape.seq_len)
        args = [aparams, spec["batch"]["tokens"]]
        in_sh = [psh, spec["shardings"]["tokens"]]
        if "prefix_embeds" in spec["batch"]:
            args.append(spec["batch"]["prefix_embeds"])
            in_sh.append(spec["shardings"]["prefix_embeds"])
        jitted = jax.jit(step, in_shardings=tuple(in_sh))
        lowered = jitted.lower(*args)
    else:  # decode
        step = make_decode_step(cfg, ctx)
        jitted = jax.jit(
            step,
            in_shardings=(psh, spec["state_shardings"],
                          spec["shardings"]["tokens"]),
            donate_argnums=(1,))
        lowered = jitted.lower(aparams, spec["state"],
                               spec["batch"]["tokens"])
    t_lower = time.time() - t0    # lint: disable=REP002 (real compile timing)
    t0 = time.time()              # lint: disable=REP002 (real compile timing)
    compiled = lowered.compile()
    t_compile = time.time() - t0  # lint: disable=REP002 (real compile timing)

    n_dev = mesh.size
    res = analyze_compiled(compiled, n_dev)
    res.update({
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "opts": {k: str(v) for k, v in (opts or {}).items()},
    })
    # roofline terms (per device, one step)
    chips = n_dev
    res["roofline"] = roofline_terms(res, cfg, shape)
    if return_artifacts:
        return res, lowered, compiled
    return res


def roofline_terms(res, cfg, shape):
    flops = res["flops"]                      # per device (SPMD program)
    hbm = res["hbm_bytes"]
    wire = res["collective_wire_total"]
    t_compute = flops / PEAK_FLOPS
    t_memory = hbm / HBM_BW
    t_coll = wire / ICI_BW
    n_dev = res["n_devices"]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens / n_dev

    # analytic fp32 optimizer streaming (outside the strict HLO op set):
    # m read+write, v read+write (fp32) + bf16 param update write
    if shape.kind == "train":
        opt_stream = (4 * 4 + 2) * cfg.param_count() / n_dev
        hbm = hbm + opt_stream
        t_memory = hbm / HBM_BW
        res["hbm_bytes_with_opt"] = hbm

    # analytic must-move bytes per device (lower bound on HBM traffic)
    pbytes = cfg.param_count() * 2 / n_dev                  # bf16 weights
    if shape.kind == "train":
        # fwd+bwd weight reads, grad write, m/v read+write (fp32)
        must_bytes = 2 * pbytes + pbytes + 4 * (cfg.param_count() * 4 / n_dev)
    elif shape.kind == "decode":
        cache = (cfg.kv_bytes_per_token(2) * shape.seq_len
                 + cfg.state_bytes_per_seq(2)) * shape.global_batch / n_dev
        must_bytes = cfg.active_param_count() * 2 / n_dev + cache
    else:  # prefill: read weights, write the cache once
        cache = cfg.kv_bytes_per_token(2) * tokens / n_dev
        must_bytes = pbytes + cache
    # Pallas-kernel-adjusted memory term: flash_core traffic lives in VMEM in
    # the runtime kernel; the kernel's own HBM I/O (q,k,v read + o write) is
    # added back analytically.
    from repro.parallel.sharding import padded_heads
    hp, kvp = padded_heads(cfg.n_heads, cfg.n_kv_heads, 16)
    kvx = kvp if shape.kind != "train" else (
        cfg.n_kv_heads if hp % cfg.n_kv_heads == 0 else kvp)
    hd = cfg.resolved_head_dim
    passes = 4 if shape.kind == "train" else 1
    if shape.kind != "decode" and cfg.n_attention_layers:
        io = (2 * hp * hd + 2 * kvx * hd) * tokens * 2 \
            * cfg.n_attention_layers * passes / n_dev
    else:
        io = 0.0
    hbm_kernel = max(hbm - res.get("flash_scoped_bytes", 0.0) + io, 0.0)
    t_memory_kernel = hbm_kernel / HBM_BW

    dom = max((t_compute, "compute"), (t_memory, "memory"), (t_coll, "collective"))
    eff = {"compute": (model_flops / flops) if flops else 0.0,
           "memory": (must_bytes / hbm) if hbm else 0.0,
           "collective": (res["collective_payload_total"] / wire) if wire else 1.0}
    return {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "t_memory_kernel_adj_s": t_memory_kernel,
        "hbm_bytes_kernel_adj": hbm_kernel,
        "bottleneck": dom[1],
        "model_flops_per_dev": model_flops,
        "must_bytes_per_dev": must_bytes,
        "useful_flop_ratio": (model_flops / flops) if flops else 0.0,
        "memory_efficiency": eff["memory"],
        "dominant_efficiency": eff[dom[1]],
        # MFU the step would achieve if it ran exactly at the binding roofline
        "roofline_fraction": (model_flops / PEAK_FLOPS) / max(
            t_compute, t_memory, t_coll) if flops else 0.0,
        "step_time_bound_s": max(t_compute, t_memory, t_coll),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--opts", default="{}",
                    help='json, e.g. {"opt_bf16": true, "remat": "none"}')
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    opts = json.loads(args.opts)
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        todo = [(a, s) for a, s, skip in cells(include_skipped=True)
                if skip is None]
    else:
        todo = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failed = []
    for arch, shape in todo:
        for mp in meshes:
            name = f"{arch}__{shape}__{'multi' if mp else 'single'}__{args.tag}"
            path = outdir / f"{name}.json"
            if path.exists() and not args.force:
                print(f"[skip existing] {name}", flush=True)
                continue
            print(f"[dryrun] {name} ...", flush=True)
            try:
                res = lower_cell(arch, shape, mp, opts)
            except Exception as e:  # record the failure, run the other cells
                failed.append(name)
                res = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single",
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-4000:]}
            path.write_text(json.dumps(res, indent=1, default=str))
            status = ("ERROR " + res["error"][:120]) if "error" in res else (
                "skipped: " + res["skipped"] if "skipped" in res else
                f"ok flops={res['flops']:.3e} hbm={res['hbm_bytes']:.3e} "
                f"wire={res['collective_wire_total']:.3e} "
                f"bottleneck={res['roofline']['bottleneck']} "
                f"frac={res['roofline']['roofline_fraction']:.3f} "
                f"compile={res['compile_s']}s")
            print(f"[done] {name}: {status}", flush=True)
    if failed:
        raise SystemExit(f"{len(failed)} cell(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
